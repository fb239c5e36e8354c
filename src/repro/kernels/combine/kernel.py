"""Pallas TPU kernel: streamed coefficient combine R = c @ X.

The gram-path rules (average / krum / multikrum / gm / mda, with or without
NNM) reduce to one linear combination of the worker stack.  The stack is
huge (n x D over the whole flattened pytree); the coefficient vector is
tiny (n,).  This kernel streams X through VMEM in (n, BLK_D) tiles and
contracts each tile against the replicated coefficient row on the MXU:

    VMEM: X_blk (n, BLK_D), c (1, n)
    MXU : r_blk = c @ X_blk          -> (1, BLK_D)

The contraction runs in X's dtype with fp32 accumulation — a bf16
transport stack is combined as bf16 bytes, matching the distributed
``tree_combine`` contract (see core/robust.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _combine_kernel(c_ref, x_ref, o_ref):
    x = x_ref[...]
    c = c_ref[...].astype(x.dtype)
    o_ref[...] = jax.lax.dot_general(
        c, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def combine_pallas(x: jax.Array, coeff: jax.Array, *, block_d: int = 512,
                   interpret: bool = False) -> jax.Array:
    """R = coeff @ X via the streamed Pallas kernel.

    Args:
      x: (n, d) stack, any d (a ragged last tile's extra columns are
        never written back).
      coeff: (n,) fp32 combination weights.
      block_d: VMEM tile width, a multiple of 128 or d itself.
      interpret: run the kernel body in the Pallas interpreter (CPU).
    Returns: (d,) fp32 combination.
    """
    n, d = x.shape
    out = pl.pallas_call(
        _combine_kernel,
        grid=(pl.cdiv(d, block_d),),
        in_specs=[
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((n, block_d), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(coeff.reshape(1, n), x)
    return out[0]
