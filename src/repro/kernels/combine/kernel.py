"""Pallas TPU kernel: streamed coefficient combine R = c @ X.

The gram-path rules (average / krum / multikrum / gm / mda, with or without
NNM) reduce to one linear combination of the worker stack.  The stack is
huge (n x D over the whole flattened pytree); the coefficient vector is
tiny (n,).  This kernel streams X through VMEM in wide (n, W) tiles and
contracts each chunk of a tile against the replicated coefficient row on
the MXU (``repro.kernels.tiling``):

    VMEM: X_blk (n, W), c (1, n)
    MXU : r_c = c @ X_c              -> its slice of the (1, W) output

The contraction runs in X's dtype with fp32 accumulation — a bf16
transport stack is combined as bf16 bytes, matching the distributed
``tree_combine`` contract (see core/robust.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


def _combine_kernel(c_ref, x_ref, o_ref, *, d: int, width: int):
    n = x_ref.shape[0]
    c = c_ref[...].astype(x_ref.dtype)

    def chunk(carry, off, w, valid):
        o_ref[:, pl.ds(off, w)] = jax.lax.dot_general(
            c, x_ref[:, pl.ds(off, w)], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return carry

    tiling.walk_chunks(pl.program_id(0), d=d, width=width, body=chunk,
                       chunk=tiling.chunk_lanes(max(tiling.SUBLANES, n)))


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def combine_pallas(x: jax.Array, coeff: jax.Array, *,
                   block_d: int | None = None,
                   interpret: bool = False) -> jax.Array:
    """R = coeff @ X via the streamed Pallas kernel.

    Args:
      x: (n, d) stack, any d (a ragged last tile's extra columns are
        never written back).
      coeff: (n,) fp32 combination weights.
      block_d: grid tile width W, a multiple of 128 or d itself; None
        picks it from x's shape and dtype (``tiling.pick_block_d``).
      interpret: run the kernel body in the Pallas interpreter (CPU).
    Returns: (d,) fp32 combination.
    """
    n, d = x.shape
    w = tiling.block_width(d, block_d, n, x.dtype)
    out = pl.pallas_call(
        functools.partial(_combine_kernel, d=d, width=w),
        grid=(tiling.grid_steps(d, w),),
        in_specs=[
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((n, w), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, w), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(coeff.reshape(1, n), x)
    return out[0]
