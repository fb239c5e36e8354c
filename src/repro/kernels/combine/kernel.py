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

A (n, R, C) leaf view (``repro.kernels.tiling``) is combined slab by
slab instead, ``r = sum_j c_j x_j`` in fp32 on the VPU, into (r, w)
blocks of an (R, C) result in the leaf's own layout.
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


def _combine_leaf_kernel(c_ref, x_ref, o_ref, *, shape: tuple,
                         block: tuple):
    n = shape[0]

    def slab(carry, row, height, valid_r, lane, width, valid_l):
        x = x_ref[:, pl.ds(row, height), pl.ds(lane, width)]
        x = x.astype(jnp.float32)
        r = c_ref[0] * x[0]
        for j in range(1, n):
            r = r + c_ref[j] * x[j]
        o_ref[pl.ds(row, height), pl.ds(lane, width)] = r
        return carry

    tiling.walk_slabs(pl.program_id(0), pl.program_id(1), shape=shape,
                      block=block, lanes=tiling.slab_lanes(n + 1, x_ref.dtype),
                      group=2, body=slab, dtype=x_ref.dtype)


def _combine_leaf(x: jax.Array, coeff: jax.Array, block_rows: int | None,
                  interpret: bool) -> jax.Array:
    n = x.shape[0]
    block = tiling.leaf_block(x.shape, block_rows, x.dtype)
    c = coeff.astype(x.dtype).astype(jnp.float32).reshape(n, 1, 1)
    return pl.pallas_call(
        functools.partial(_combine_leaf_kernel, shape=x.shape, block=block),
        grid=tiling.leaf_grid(x.shape, block),
        in_specs=[
            pl.BlockSpec((n, 1, 1), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((n, *block), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec(block, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape[1:], jnp.float32),
        interpret=interpret,
    )(c, x)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def combine_pallas(x: jax.Array, coeff: jax.Array, *,
                   block_d: int | None = None,
                   interpret: bool = False) -> jax.Array:
    """R = coeff @ X via the streamed Pallas kernel.

    Args:
      x: (n, d) stack, any d (a ragged last tile's extra columns are
        never written back); or a (n, R, C) leaf view.
      coeff: (n,) fp32 combination weights.
      block_d: grid tile width W, a multiple of 128 or d itself; rows r
        of a leaf view.  None picks it from x's shape and dtype
        (``tiling.pick_block_d``, ``tiling.pick_leaf_block``).
      interpret: run the kernel body in the Pallas interpreter (CPU).
    Returns: (d,) fp32 combination; (R, C) for a leaf view.
    """
    if x.ndim == 3:
        return _combine_leaf(x, coeff, block_d, interpret)
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
