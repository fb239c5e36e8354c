"""Jit'd public wrapper for the Gram kernel (dispatch; any d, no padding)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.gram.kernel import gram_batched_pallas, gram_pallas
from repro.kernels.gram.ref import gram_batched_ref, gram_ref
from repro.kernels.target import resolve_interpret


@functools.partial(jax.jit, static_argnames=("block_d", "use_pallas", "interpret"))
def gram(x: jax.Array, *, block_d: int = 512, use_pallas: bool = True,
         interpret: bool | None = None) -> jax.Array:
    """Gram matrix of a (n, d) stack.

    Runs the Pallas kernel over any d (a ragged last tile is masked
    in-kernel; a d below ``block_d`` is one full-width tile, so it is the
    oracle's contraction verbatim), or the jnp oracle when
    ``use_pallas=False``.  ``interpret=None`` resolves to True off-TPU so
    the same call site works everywhere.
    """
    if not use_pallas:
        return gram_ref(x)
    return gram_pallas(x, block_d=min(block_d, x.shape[1]),
                       interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_d", "use_pallas", "interpret"))
def gram_batched(x: jax.Array, *, block_d: int = 512, use_pallas: bool = True,
                 interpret: bool | None = None) -> jax.Array:
    """Per-lane Gram matrices of a (B, n, d) lane-batched stack.

    Same any-d contract as :func:`gram`; the whole fleet bucket runs as
    one kernel launch with grid = lanes x d-blocks.
    """
    if not use_pallas:
        return gram_batched_ref(x)
    return gram_batched_pallas(x, block_d=min(block_d, x.shape[2]),
                               interpret=resolve_interpret(interpret))
