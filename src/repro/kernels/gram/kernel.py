"""Pallas TPU kernel: blocked Gram matrix accumulation.

The robust-aggregation hot spot is the O(n^2 d) pairwise structure over the
worker gradient stack.  On TPU we stream the (n, d) stack through VMEM in
(n, BLK_D) tiles and accumulate the tiny (n, n) Gram matrix with the MXU:

    HBM:  X (n, d)                      --- d is huge (per-shard params)
    VMEM: X_blk (n, BLK_D)              --- one tile per grid step
    MXU:  G += X_blk @ X_blk^T          --- (n, BLK_D) x (BLK_D, n)

n is the worker count (16 / 32; multiple of 8 so the sublane dim is
hardware-aligned) and BLK_D is a multiple of 128 (lane dim / MXU-aligned).
The (n, n) accumulator lives in the output VMEM block, revisited by every
grid step (standard reduce-into-output pattern).  A ragged last tile
(d not a multiple of BLK_D) reads unspecified values past d, so its
out-of-range columns are zeroed in-kernel before the contraction — the
stack is never copied to a padded width in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _tile(x: jax.Array, i, d: int) -> jax.Array:
    """fp32 tile i of a (n, d) stream, columns past d zeroed (a select, so
    whatever the ragged tile held past d never reaches the sum)."""
    x = x.astype(jnp.float32)
    blk = x.shape[1]
    if d % blk == 0:
        return x
    col = i * blk + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(col < d, x, 0.0)


def _gram_kernel(x_ref, o_ref, *, d: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = _tile(x_ref[...], i, d)
    o_ref[...] += jax.lax.dot_general(
        x, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _gram_batched_kernel(x_ref, o_ref, *, d: int):
    # d-block index is the LAST grid dim (innermost on TPU), so for a fixed
    # lane the (1, n, n) accumulator block is revisited across d steps.
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = _tile(x_ref[0], i, d)
    o_ref[0] += jax.lax.dot_general(
        x, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def gram_pallas(x: jax.Array, *, block_d: int = 512, interpret: bool = False
                ) -> jax.Array:
    """G = X X^T via the blocked Pallas kernel.

    Args:
      x: (n, d) stack, any d.
      block_d: VMEM tile width, a multiple of 128 or d itself.
      interpret: run the kernel body in the Pallas interpreter (CPU).
    """
    n, d = x.shape
    return pl.pallas_call(
        functools.partial(_gram_kernel, d=d),
        grid=(pl.cdiv(d, block_d),),
        in_specs=[pl.BlockSpec((n, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def gram_batched_pallas(x: jax.Array, *, block_d: int = 512,
                        interpret: bool = False) -> jax.Array:
    """Lane-batched Gram: (B, n, d) -> (B, n, n) in ONE kernel launch.

    Grid = lanes x d-blocks; each lane accumulates its own (n, n) output
    block over the d sweep.  One compile serves every lane of a fleet shape
    bucket — the standalone analogue of what the vmap batching rule does to
    :func:`gram_pallas` inside the lane-vmapped round.
    """
    b, n, d = x.shape
    return pl.pallas_call(
        functools.partial(_gram_batched_kernel, d=d),
        grid=(b, pl.cdiv(d, block_d)),
        in_specs=[pl.BlockSpec((1, n, block_d), lambda l, i: (l, 0, i))],
        out_specs=pl.BlockSpec((1, n, n), lambda l, i: (l, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, n), jnp.float32),
        interpret=interpret,
    )(x)
