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


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def gram_pallas(x: jax.Array, *, block_d: int | None = None,
                interpret: bool = False) -> jax.Array:
    """G = X X^T via the blocked Pallas kernel.

    Args:
      x: (n, d) stack, any d.
      block_d: grid tile width W, a multiple of 128 or d itself; None
        picks it from x's shape and dtype (``tiling.pick_block_d``).
      interpret: run the kernel body in the Pallas interpreter (CPU).
    """
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
