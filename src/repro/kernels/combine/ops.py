"""Jit'd public wrapper for the streamed combine kernel (any d)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.combine.kernel import combine_pallas
from repro.kernels.combine.ref import combine_ref
from repro.kernels.target import resolve_interpret


@functools.partial(jax.jit, static_argnames=("block_d", "use_pallas",
                                             "interpret"))
def combine(x: jax.Array, coeff: jax.Array, *, block_d: int = 512,
            use_pallas: bool = True,
            interpret: bool | None = None) -> jax.Array:
    """Linear combination coeff @ X of a (n, d) stack.

    Runs the Pallas kernel over any d (per-column math: a ragged last tile
    needs no mask, its out-of-range columns are never written), or the jnp
    oracle when ``use_pallas=False``.
    """
    if not use_pallas:
        return combine_ref(x, coeff)
    return combine_pallas(x, coeff, block_d=min(block_d, x.shape[1]),
                          interpret=resolve_interpret(interpret))
