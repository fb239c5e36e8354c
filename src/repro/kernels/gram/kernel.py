"""Pallas TPU kernel: blocked Gram matrix accumulation.

The robust-aggregation hot spot is the O(n^2 d) pairwise structure over the
worker gradient stack.  On TPU we stream the (n, d) stack through VMEM in
wide (n, W) tiles and accumulate the tiny (n, n) Gram matrix with the MXU,
one CHUNK-lane slice of the tile at a time (``repro.kernels.tiling``):

    HBM:  X (n, d)                      --- d is huge (per-shard params)
    VMEM: X_blk (n, W)                  --- one tile per grid step
    MXU:  G += X_c @ X_c^T              --- per (n, CHUNK) chunk of X_blk

W is as wide as VMEM allows, so the grid takes few steps; the chunks are
the same CHUNK-wide column ranges, summed in the same order, whatever W
is.  The (n, n) accumulator lives in the output VMEM block, revisited by
every grid step (standard reduce-into-output pattern).  The chunk at the
ragged end of d reads unspecified values past d, so its out-of-range
columns are zeroed in-kernel before the contraction — the stack is never
copied to a padded width in HBM.

A leaf view ``(n, R, C)`` (``repro.kernels.tiling``: the single-device
stack, one leaf at a time, in the leaf's own layout) is read in
``(n, r, w)`` blocks instead.  There each worker's data is its own
``(rows, lanes)`` slab of vregs, so the Gram is elementwise fp32 on the
VPU: ``A[j, k] += x_j * x_k`` for every pair of workers, slab by slab,
the (n, n, rows, lanes) accumulator held in vregs across a lane piece's
rows, then added into the kernel's output block, which every grid step
revisits; the wrapper sums its rows and lanes into G[j, k].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


def _accumulate(x_ref, o_ref, step, *, d: int, width: int, lead=()):
    """Add the Gram of grid step ``step``'s tile ``x_ref[lead]`` to
    ``o_ref[lead]`` (zeroed at step 0), one CHUNK-lane product at a time
    in column order (GROUP products to a loop iteration), the ragged
    end's columns zeroed."""
    @pl.when(step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def chunk(acc, off, w, valid):
        x = x_ref[(*lead, slice(None), pl.ds(off, w))].astype(jnp.float32)
        x = tiling.valid_columns(x, valid)
        return acc + jax.lax.dot_general(
            x, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def finish(acc):
        o_ref[(*lead, ...)] = acc

    tiling.walk_chunks(step, d=d, width=width, chunk=tiling.CHUNK,
                       group=tiling.GROUP, body=chunk,
                       init=o_ref[(*lead, ...)], finish=finish)


def _gram_kernel(x_ref, o_ref, *, d: int, width: int):
    _accumulate(x_ref, o_ref, pl.program_id(0), d=d, width=width)


def _gram_batched_kernel(x_ref, o_ref, *, d: int, width: int):
    # d-block index is the LAST grid dim (innermost on TPU), so for a fixed
    # lane the (1, n, n) accumulator block is revisited across d steps.
    _accumulate(x_ref, o_ref, pl.program_id(1), d=d, width=width, lead=(0,))


def _gram_leaf_kernel(x_ref, o_ref, *, shape: tuple, block: tuple):
    """Slab sums of the Gram of a (n, R, C) leaf view (module docs)."""
    n = shape[0]
    rows = tiling.row_tile(x_ref.dtype)
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def init(width):
        return jnp.zeros((n, n, rows, width), jnp.float32)

    def slab(acc, row, height, valid_r, lane, width, valid_l):
        x = x_ref[:, pl.ds(row, height), pl.ds(lane, width)]
        x = x.astype(jnp.float32)
        if height < rows:           # R below one tile: pad with zero rows
            x = jnp.concatenate(
                [x, jnp.zeros((n, rows - height, width), jnp.float32)], 1)
        x = tiling.valid_columns(tiling.valid_rows(x, valid_r), valid_l)
        return acc + x[:, None] * x[None, :]

    def finish(acc, lane, width):
        o_ref[:, :, :, pl.ds(0, width)] += acc

    tiling.walk_slabs(i, j, shape=shape, block=block,
                      lanes=tiling.gram_lanes(n, x_ref.dtype), group=4,
                      body=slab, init=init, finish=finish,
                      dtype=x_ref.dtype)


def _gram_leaf(x: jax.Array, block_rows: int | None,
               interpret: bool) -> jax.Array:
    n = x.shape[0]
    block = tiling.leaf_block(x.shape, block_rows, x.dtype)
    acc = (n, n, tiling.row_tile(x.dtype),
           min(tiling.gram_lanes(n, x.dtype), block[1]))
    sums = pl.pallas_call(
        functools.partial(_gram_leaf_kernel, shape=x.shape, block=block),
        grid=tiling.leaf_grid(x.shape, block),
        in_specs=[pl.BlockSpec((n, *block), lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec(acc, lambda i, j: (0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(acc, jnp.float32),
        interpret=interpret,
    )(x)
    return jnp.sum(sums, axis=(2, 3))


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def gram_pallas(x: jax.Array, *, block_d: int | None = None,
                interpret: bool = False) -> jax.Array:
    """G = X X^T via the blocked Pallas kernel.

    Args:
      x: (n, d) stack, any d; or a (n, R, C) leaf view, read in its own
        layout (``tiling.leaf_shape``).
      block_d: grid tile: width W of a (n, d) stack, a multiple of 128
        or d itself; rows r of a leaf view, a multiple of its row tile
        or R.  None picks it from x's shape and dtype
        (``tiling.pick_block_d``, ``tiling.pick_leaf_block``).
      interpret: run the kernel body in the Pallas interpreter (CPU).
    """
    if x.ndim == 3:
        return _gram_leaf(x, block_d, interpret)
    n, d = x.shape
    w = tiling.block_width(d, block_d, n, x.dtype)
    return pl.pallas_call(
        functools.partial(_gram_kernel, d=d, width=w),
        grid=(tiling.grid_steps(d, w),),
        in_specs=[pl.BlockSpec((n, w), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def gram_batched_pallas(x: jax.Array, *, block_d: int | None = None,
                        interpret: bool = False) -> jax.Array:
    """Lane-batched Gram: (B, n, d) -> (B, n, n) in ONE kernel launch.

    Grid = lanes x d-blocks; each lane accumulates its own (n, n) output
    block over the d sweep.  One compile serves every lane of a fleet shape
    bucket — the standalone analogue of what the vmap batching rule does to
    :func:`gram_pallas` inside the lane-vmapped round.
    """
    b, n, d = x.shape
    w = tiling.block_width(d, block_d, n, x.dtype)
    return pl.pallas_call(
        functools.partial(_gram_batched_kernel, d=d, width=w),
        grid=(b, tiling.grid_steps(d, w)),
        in_specs=[pl.BlockSpec((1, n, w), lambda l, i: (l, 0, i))],
        out_specs=pl.BlockSpec((1, n, n), lambda l, i: (l, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, n), jnp.float32),
        interpret=interpret,
    )(x)
