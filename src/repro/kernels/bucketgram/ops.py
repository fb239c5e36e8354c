"""Jit'd public wrapper for the fused bucketed-gram kernel (padding +
dispatch)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bucketgram.kernel import bucketgram_pallas
from repro.kernels.bucketgram.ref import bucket_means_gram_ref
from repro.kernels.target import resolve_interpret


def _ceil_to(v: int, m: int) -> int:
    return -(-v // m) * m


def pick_block_n(n: int, cap: int = 512) -> int:
    """VMEM tile height for the n sweep: lane-dim multiple of 128 (the B
    tile is (n_b, BLK_N)), smallest covering n for small stacks."""
    if n >= cap:
        return cap
    return max(128, _ceil_to(n, 128))


@functools.partial(jax.jit,
                   static_argnames=("with_gram", "block_n", "block_d",
                                    "use_pallas", "interpret"))
def bucket_means_gram(x: jax.Array, bmat: jax.Array, *,
                      with_gram: bool = True,
                      block_n: int | None = None,
                      block_d: int | None = None,
                      use_pallas: bool = True,
                      interpret: bool | None = None
                      ) -> tuple[jax.Array, jax.Array | None]:
    """Bucket means (and optionally their reduced Gram) of a (n, d) stack.

    ``bmat`` is the (n_b, n) row-normalized assignment matrix
    (:func:`repro.core.bucketing.bucket_matrix`).  Returns
    ``(means (n_b, d) in x.dtype, gram (n_b, n_b) fp32 | None)``.

    Padding (all exact): n_b up to a multiple of 8 with zero ROWS of B
    (zero mean rows / zero gram border, sliced off), n up to a multiple of
    ``block_n`` with zero columns of B + zero rows of X (contribute
    nothing), d up to a multiple of ``block_d`` with zero columns of X.
    ``use_pallas=False`` runs the jnp oracle; ``interpret=None`` resolves
    to True off-TPU.
    """
    if not use_pallas:
        return bucket_means_gram_ref(x, bmat, with_gram=with_gram)
    interpret = resolve_interpret(interpret)
    n, d = x.shape
    n_b = bmat.shape[0]
    bn = block_n if block_n is not None else pick_block_n(n)
    bd = block_d if block_d is not None else min(512, max(128, _ceil_to(d, 128)))
    pad_nb = (-n_b) % 8
    pad_n = (-n) % bn
    pad_d = (-d) % bd
    if pad_n or pad_d:
        x = jnp.pad(x, ((0, pad_n), (0, pad_d)))
    if pad_nb or pad_n:
        bmat = jnp.pad(bmat, ((0, pad_nb), (0, pad_n)))
    y, g = bucketgram_pallas(x, bmat, block_n=bn, block_d=bd,
                             with_gram=with_gram, interpret=interpret)
    y = y[:n_b, :d].astype(x.dtype)
    if g is None:
        return y, None
    return y, g[:n_b, :n_b]
