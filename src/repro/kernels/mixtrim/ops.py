"""Jit'd public wrapper for the fused mix+trim kernel."""
from __future__ import annotations

import jax

from repro.kernels.target import resolve_interpret
from repro.kernels.mixtrim.kernel import _jit_by_f, mixtrim_pallas
from repro.kernels.mixtrim.ref import mixtrim_ref


@_jit_by_f(static_argnames=("mode", "block_d", "use_pallas", "interpret"))
def mixtrim(x: jax.Array, m: jax.Array, f, *, mode: str = "trim",
            block_d: int | None = None, use_pallas: bool = True,
            interpret: bool | None = None) -> jax.Array:
    """Fused NNM-mix + coordinate-wise trim/median of a (n, d) stack.

    ``m=None`` elides the mix dot entirely (plain CWTM/CWMed).  ``f`` is a
    python int (compiled in) or a traced int32 scalar (a rank mask: one
    compile serves every f of a shape bucket, possibly a vmap lane
    tracer).  Any d, in ``block_d``-wide grid tiles (None: the widest that
    fits VMEM, ``tiling.pick_block_d``): the columns of a ragged last tile
    past d are never written back, so the stack is never copied to a
    padded width.  Non-power-of-two n runs the padded sentinel bitonic
    sort (see kernel.py) — the jnp oracle is used only when
    ``use_pallas=False``.
    """
    if not use_pallas:
        return mixtrim_ref(x, m, f, mode)
    return mixtrim_pallas(x, m, f, mode=mode, block_d=block_d,
                          interpret=resolve_interpret(interpret))
