"""Pallas TPU kernel: fused NNM-mix + coordinate-wise trim/median.

For coordinate-wise rules (CWTM / CWMed) after NNM, the naive pipeline
materializes the mixed stack Y = M @ X in HBM (n x |shard| extra bytes) and
reads it back for the sort.  This kernel fuses the three stages per VMEM
tile so Y never leaves VMEM:

    VMEM: X_blk (n, BLK_D), M (n, n)
    MXU : Y_blk = M @ X_blk
    VPU : bitonic sort network along the (small) worker dim
    out : trimmed mean / median of Y_blk  ->  (1, BLK_D)

The sort is a static bitonic network (log^2 n compare-exchange stages built
from sublane rotations + min/max + select), because dynamic gathers along
the sublane dimension do not map to the TPU vector unit.  The network needs a
power-of-two height of at least one sublane tile (8 rows); when n is not
one (the common federated case, e.g. the paper's n=17, or n=4), the
worker dim is padded up to it in VMEM with fp32-max sentinel rows.  Ascending sort parks every sentinel above
every finite value, so the real rows occupy sorted positions 0..n-1
exactly as in the unpadded sort and the trim/median ranks simply ignore
the sentinel tail — no jnp-oracle fallback, the fused kernel runs for
every n.  (Caveat: a worker value equal to fp32 max would tie with the
sentinels; gradients never are.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Sentinel for the padded sort: sorts above every finite worker value.
_SENTINEL = float(np.finfo(np.float32).max)

#: Rows of one fp32 vreg tile.
_SUBLANES = 8


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (the bitonic network height)."""
    return 1 << (n - 1).bit_length()


def _compare_swap(y: jax.Array, j: int, lower: jax.Array,
                  keep_min: jax.Array) -> jax.Array:
    """One bitonic compare-exchange of every row i with its partner i XOR j.

    The partner row comes from two sublane rotations (``pltpu.roll``
    follows ``jnp.roll``: ``roll(y, s)[i] = y[i - s]``) picked by whether
    bit j of i is clear — static shapes and shifts only, which Mosaic
    lowers (a reversed or 4-D reshaped view does not)."""
    n = y.shape[0]
    above = pltpu.roll(y, n - j, 0)        # y[i + j]
    below = pltpu.roll(y, j, 0)            # y[i - j]
    partner = jnp.where(lower, above, below)
    return jnp.where(keep_min, jnp.minimum(y, partner),
                     jnp.maximum(y, partner))


def _bitonic_sort(y: jax.Array) -> jax.Array:
    """Sort (n, blk) along axis 0 ascending; n must be a power of two."""
    n = y.shape[0]
    # >=2-D iota: 1-D iota does not lower on TPU.
    row = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    k = 2
    while k <= n:
        ascending = (row & k) == 0
        j = k // 2
        while j >= 1:
            lower = (row & j) == 0          # i is the low index of its pair
            y = _compare_swap(y, j, lower, lower == ascending)
            j //= 2
        k *= 2
    return y


def sort_height(n: int) -> int:
    """Rows of the bitonic network for n workers: a power of two, and at
    least one full sublane tile (8 rows) so every rotation is whole-tile."""
    return max(_SUBLANES, next_pow2(n))


def _with_sentinels(y: jax.Array, n_real: int) -> jax.Array:
    """Bring y to the bitonic network height with sentinel pad rows.

    The mix path arrives already tall (the zero-row-padded M made the dot
    produce (n_pad, blk)) and gets its pad rows overwritten; the no-mix
    path arrives at its true height and gets sentinel rows appended
    IN-KERNEL — cheaper than a host-side (n_pad, D) zero-padded copy of
    the whole stack, which would re-materialize exactly the wide HBM
    intermediate this kernel exists to avoid."""
    n_pad = sort_height(n_real)
    if n_pad == n_real:
        return y
    if y.shape[0] == n_real:
        tail = jnp.zeros((n_pad - n_real, y.shape[1]), jnp.float32)
        y = jnp.concatenate([y, tail])
    i = jax.lax.broadcasted_iota(jnp.int32, (n_pad, 1), 0)
    return jnp.where(i < n_real, y, _SENTINEL)


def _reduce_sorted(ys: jax.Array, f, mode: str, n_real: int) -> jax.Array:
    """(n_pad, blk) ascending columns -> (1, blk) trimmed mean / median.

    Trimming sums the kept ranks one row at a time, in rank order: the
    order of the oracles' column sums, and one that no tile width can
    change (a ``sum(axis=0)`` lets the compiler reassociate), so interpret
    mode matches ``mixtrim_ref`` / ``mixtrim_dyn_ref`` bit for bit and a
    D-sharded run matches a single-device one.  A python-int ``f`` sums
    ranks f..n_real-f-1; a traced scalar ``f`` (the dynamic kernel) weights
    every real rank by ``f <= r < n_real - f``.  No sentinel row (rank >=
    n_real) is ever read."""
    if mode == "med":
        mid = n_real // 2
        if n_real % 2:
            return ys[mid:mid + 1]
        return 0.5 * (ys[mid - 1:mid] + ys[mid:mid + 1])
    if mode != "trim":
        raise ValueError(mode)
    static = isinstance(f, int)
    total = jnp.full((1, ys.shape[1]), jnp.nan, jnp.float32)  # nothing kept
    for i, r in enumerate(range(f, n_real - f) if static else range(n_real)):
        row = ys[r:r + 1]
        if not static:
            row = row * ((r >= f) & (r < n_real - f)).astype(jnp.float32)
        total = row if i == 0 else total + row
    if static:
        return total / (n_real - 2 * f)
    return total / jnp.maximum((n_real - 2 * f).astype(jnp.float32), 1.0)


def _make_kernel(mode: str, mix: bool, n_real: int, f=None):
    """Kernel body.  ``f=None`` reads the trim count from a leading (1,)
    int32 SMEM operand (the dynamic kernel: one compile serves every
    Byzantine budget of a fleet shape bucket); ``mode="med"`` ignores f.
    ``mix=False`` drops the M operand and the MXU dot entirely (plain
    CWTM/CWMed).  ``n_real`` is the true worker count; any pad rows of
    the sort become sentinels before the network runs."""
    def kernel(*refs):
        refs = list(refs)
        f_val = f if f is not None else refs.pop(0)[0]
        if mix:
            m_ref, x_ref, o_ref = refs
        else:
            x_ref, o_ref = refs
        x = x_ref[...].astype(jnp.float32)
        if mix:
            # M is (n_pad, n_real): zero pad rows, so Y's pad rows are 0
            # until the sentinel mask overwrites them.
            m = m_ref[...].astype(jnp.float32)
            y = jax.lax.dot_general(
                m, x, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            y = x
        ys = _bitonic_sort(_with_sentinels(y, n_real))
        o_ref[...] = _reduce_sorted(ys, f_val, mode, n_real)
    return kernel


def _mixtrim_call(x, m, f, *, mode: str, block_d: int, interpret: bool):
    """The pallas_call behind both entry points: grid over the d tiles
    (a ragged last tile is masked by the pipeline, never padded in HBM);
    M, zero-row-padded to the sort height, is broadcast to every grid
    step; a traced ``f`` rides in scalar memory."""
    n, d = x.shape
    n_pad = sort_height(n)
    in_specs = [pl.BlockSpec((n, block_d), lambda i: (0, i))]
    operands = [x]
    if m is not None:
        # Zero pad rows: the mix dot then produces the taller stack
        # directly (their values are overwritten by sentinels in-kernel).
        m = jnp.pad(m, ((0, n_pad - n), (0, 0)))
        in_specs.insert(0, pl.BlockSpec((n_pad, n), lambda i: (0, 0)))
        operands.insert(0, m)
    static_f = None
    if isinstance(f, int):
        static_f = f
    else:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.insert(0, jnp.asarray(f, jnp.int32).reshape(1))
    out = pl.pallas_call(
        _make_kernel(mode, m is not None, n, static_f),
        grid=(pl.cdiv(d, block_d),),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(*operands)
    return out[0]


@functools.partial(jax.jit,
                   static_argnames=("f", "mode", "block_d", "interpret"))
def mixtrim_pallas(x: jax.Array, m: jax.Array, *, f: int, mode: str = "trim",
                   block_d: int = 512, interpret: bool = False) -> jax.Array:
    """Fused (M @ X -> sort -> trim/median) over d tiles.

    Args:
      x: (n, d) worker stack, any n >= 1, any d (block_d a multiple of
        128).  Non-power-of-two n runs the padded sentinel sort (see
        module docs).
      m: (n, n) mixing matrix, or None for plain CWTM/CWMed (the mix dot
        is elided entirely — no identity matmul).
      f: trim count (ignored for mode="med").
      mode: "trim" or "med".
    Returns: (d,) fp32 aggregate.
    """
    return _mixtrim_call(x, m, int(f), mode=mode, block_d=block_d,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("mode", "block_d", "interpret"))
def mixtrim_dyn_pallas(x: jax.Array, m: jax.Array, f: jax.Array, *,
                       mode: str = "trim", block_d: int = 512,
                       interpret: bool = False) -> jax.Array:
    """Fused mix+trim with a TRACED Byzantine count.

    Same tiling as :func:`mixtrim_pallas` (including the padded sentinel
    sort for non-power-of-two n); ``f`` rides along as a (1,) int32
    scalar-memory operand, and trimming goes through a rank mask.  Under
    ``jax.vmap`` (the fleet's lane axis) the pallas batching rule prepends
    a lane grid dimension, so a whole shape bucket still costs one
    compile.
    """
    return _mixtrim_call(x, m, jnp.asarray(f, jnp.int32), mode=mode,
                         block_d=block_d, interpret=interpret)
