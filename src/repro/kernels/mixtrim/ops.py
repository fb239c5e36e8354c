"""Jit'd public wrapper for the fused mix+trim kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.target import resolve_interpret
from repro.kernels.mixtrim.kernel import mixtrim_dyn_pallas, mixtrim_pallas
from repro.kernels.mixtrim.ref import mixtrim_dyn_ref, mixtrim_ref


@functools.partial(jax.jit, static_argnames=("f", "mode", "block_d",
                                             "use_pallas", "interpret"))
def mixtrim(x: jax.Array, m: jax.Array, *, f: int, mode: str = "trim",
            block_d: int | None = None, use_pallas: bool = True,
            interpret: bool | None = None) -> jax.Array:
    """Fused NNM-mix + coordinate-wise trim/median of a (n, d) stack.

    ``m=None`` elides the mix dot entirely (plain CWTM/CWMed).  Any d, in
    ``block_d``-wide grid tiles (None: the widest that fits VMEM,
    ``tiling.pick_block_d``): the columns of a ragged last tile past d are
    never written back, so the stack is never copied to a padded width.
    Non-power-of-two n runs the padded sentinel bitonic sort (see
    kernel.py) — the jnp oracle is used only when ``use_pallas=False``.
    """
    if not use_pallas:
        return mixtrim_ref(x, m, f, mode)
    return mixtrim_pallas(x, m, f=f, mode=mode, block_d=block_d,
                          interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("mode", "block_d", "use_pallas",
                                             "interpret"))
def mixtrim_dyn(x: jax.Array, m: jax.Array, f: jax.Array, *,
                mode: str = "trim", block_d: int | None = None,
                use_pallas: bool = True,
                interpret: bool | None = None) -> jax.Array:
    """Fused mix+trim with a TRACED trim count (fleet dynamic-f path).

    One compile serves every f of a shape bucket: ``f`` is an int32 scalar
    operand (possibly a vmap lane tracer), trimming is a rank mask over the
    sorted stack.  Same ``m=None`` / any-d / sentinel-padded-sort contract
    as :func:`mixtrim`.
    """
    if not use_pallas:
        return mixtrim_dyn_ref(x, m, f, mode)
    return mixtrim_dyn_pallas(x, m, f, mode=mode, block_d=block_d,
                              interpret=resolve_interpret(interpret))
