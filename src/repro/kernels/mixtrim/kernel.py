"""Pallas TPU kernel: fused NNM-mix + coordinate-wise trim/median.

For coordinate-wise rules (CWTM / CWMed) after NNM, the naive pipeline
materializes the mixed stack Y = M @ X in HBM (n x |shard| extra bytes) and
reads it back for the sort.  This kernel fuses the three stages per VMEM
tile so Y never leaves VMEM:

    VMEM: X_blk (n, W), M (n, n)       --- one wide tile per grid step
    MXU : Y_c = M @ X_c                --- per (n, C) chunk of X_blk
    VPU : bitonic sort network along the (small) worker dim
    out : trimmed mean / median of Y_c  ->  its (1, C) output slice

The grid tile W is as wide as VMEM allows and the chunk C holds about 32
vregs of the sort network (``repro.kernels.tiling``: 4096 lanes at n <= 8,
narrower for more workers).

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

That is the flat form, for a (n, d) stack or shard, whose workers share
the sublanes of each tile.  A (n, R, C) leaf view (``tiling``: the
single-device stack, one leaf at a time in the leaf's own layout) gives
each worker its own (rows, lanes) slab of vregs instead:

    VMEM: X_blk (n, r, w), M's columns (n, 1, 1) each
    VPU : Y_k = sum_j M[k, j] X_j      --- scalar x slab, summed in fp32
    VPU : slab sort: min/max of whole slabs, no rolls, no sentinels
    out : trimmed mean / median        ->  its (rows, lanes) output piece

On the chip the mix's operands are first rounded to bf16, the precision
of the flat form's one-pass MXU dot, so both forms aggregate alike; the
interpreter's mix is exact, as its dot is.

The slab sort is Batcher's odd-even merge network for n values
(:func:`sort_pairs`), each compare-exchange a ``minimum`` and a
``maximum`` of two slabs; the pairs that would touch a pad row are
simply left out, so n=4 sorts 4 slabs in 5 pairs and nothing is padded.
The output block has the leaf's own layout, so the aggregate reshapes back
to the leaf for free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling
from repro.kernels.tiling import next_pow2, sort_height

#: Live slabs per worker of the leaf-view body (its input and its mixed
#: slab), which set its slab lanes: 512 at n=4.
MIXTRIM_SLABS = 2

#: Sentinel for the padded sort: sorts above every finite worker value.
_SENTINEL = float(np.finfo(np.float32).max)


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


def _bitonic_sort(y: jax.Array, n_real: int) -> jax.Array:
    """Sort the first next_pow2(n_real) rows of (n, blk) along axis 0
    ascending; n is a power of two, at least that height.  The rows from
    that height on are neither read nor ordered: with n_real <= n / 2 the
    network's last merge stages would only compare real rows with
    sentinels, and change nothing."""
    n = y.shape[0]
    # >=2-D iota: 1-D iota does not lower on TPU.
    row = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    k = 2
    while k <= next_pow2(n_real):
        ascending = (row & k) == 0
        j = k // 2
        while j >= 1:
            lower = (row & j) == 0          # i is the low index of its pair
            y = _compare_swap(y, j, lower, lower == ascending)
            j //= 2
        k *= 2
    return y


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


def _reduce_ranks(rank, f, mode: str, n_real: int) -> jax.Array:
    """Trimmed mean / median of n_real ascending ranks, ``rank(r)`` the
    r-th (each a (rows, lanes) value).

    Trimming sums the kept ranks one at a time, in rank order: the
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
            return rank(mid)
        return 0.5 * (rank(mid - 1) + rank(mid))
    if mode != "trim":
        raise ValueError(mode)
    static = isinstance(f, int)
    ranks = range(f, n_real - f) if static else range(n_real)
    if not ranks:                                          # nothing kept
        return jnp.full(rank(0).shape, jnp.nan, jnp.float32)
    for i, r in enumerate(ranks):
        row = rank(r)
        if not static:
            row = row * ((r >= f) & (r < n_real - f)).astype(jnp.float32)
        total = row if i == 0 else total + row
    if static:
        return total / (n_real - 2 * f)
    return total / jnp.maximum((n_real - 2 * f).astype(jnp.float32), 1.0)


def _reduce_sorted(ys: jax.Array, f, mode: str, n_real: int) -> jax.Array:
    """(n_pad, blk) ascending columns -> (1, blk) trimmed mean / median
    (:func:`_reduce_ranks` over the real rows)."""
    return _reduce_ranks(lambda r: ys[r:r + 1], f, mode, n_real)


def sort_pairs(n: int) -> list[tuple[int, int]]:
    """Compare-exchanges of Batcher's odd-even merge sort of n values, in
    order: the network for next_pow2(n), less every pair that touches an
    index from n on (those stand for +inf, which no pair moves)."""
    size = next_pow2(n)
    pairs = []
    p = 1
    while p < size:
        k = p
        while k >= 1:
            for j in range(k % p, size - k, 2 * k):
                for i in range(min(k, size - j - k)):
                    if (i + j) // (2 * p) == (i + j + k) // (2 * p):
                        pairs.append((i + j, i + j + k))
            k //= 2
        p *= 2
    return [(a, b) for a, b in pairs if b < n]


def _sort_slabs(ys: list) -> list:
    """Sort a list of equal-shape slabs elementwise, ascending (the
    :func:`sort_pairs` network: a min and a max per pair)."""
    ys = list(ys)
    for a, b in sort_pairs(len(ys)):
        ys[a], ys[b] = jnp.minimum(ys[a], ys[b]), jnp.maximum(ys[a], ys[b])
    return ys


def _make_kernel(mode: str, mix: bool, n_real: int, f, *, d: int,
                 width: int):
    """Kernel body.  ``f=None`` reads the trim count from a leading (1, 1)
    int32 SMEM operand (the dynamic kernel: one compile serves every
    Byzantine budget of a fleet shape bucket); ``mode="med"`` ignores f.
    ``mix=False`` drops the M operand and the MXU dot entirely (plain
    CWTM/CWMed).  ``n_real`` is the true worker count; any pad rows of
    the sort become sentinels before the network runs.  The (n, width)
    grid tile is mixed, sorted and reduced one chunk at a time
    (``tiling.walk_chunks``), each chunk's columns written to its slice
    of the output row; columns past d are computed but never written
    back, so nothing is masked."""
    def kernel(*refs):
        refs = list(refs)
        f_val = f if f is not None else refs.pop(0)[0, 0]
        if mix:
            m_ref, x_ref, o_ref = refs
            # M is (n_pad, n_real): zero pad rows, so Y's pad rows are 0
            # until the sentinel mask overwrites them.
            m = m_ref[...].astype(jnp.float32)
        else:
            x_ref, o_ref = refs

        def chunk(carry, off, w, valid):
            x = x_ref[:, pl.ds(off, w)].astype(jnp.float32)
            y = jax.lax.dot_general(
                m, x, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) if mix else x
            ys = _bitonic_sort(_with_sentinels(y, n_real), n_real)
            o_ref[:, pl.ds(off, w)] = _reduce_sorted(ys, f_val, mode, n_real)
            return carry

        tiling.walk_chunks(pl.program_id(0), d=d, width=width, body=chunk,
                           chunk=tiling.chunk_lanes(sort_height(n_real)))
    return kernel


def _mixtrim_call(x, m, f, *, mode: str, block_d: int | None,
                  interpret: bool):
    """The pallas_call behind both entry points: grid over wide d tiles
    (a ragged last tile is never padded in HBM); M, zero-row-padded to
    the sort height, is broadcast to every grid step; a traced ``f``
    rides in scalar memory."""
    n, d = x.shape
    n_pad = sort_height(n)
    w = tiling.block_width(d, block_d, n, x.dtype)
    in_specs = [pl.BlockSpec((n, w), lambda i: (0, i))]
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
        operands.insert(0, jnp.asarray(f, jnp.int32).reshape(1, 1))
    out = pl.pallas_call(
        _make_kernel(mode, m is not None, n, static_f, d=d, width=w),
        grid=(tiling.grid_steps(d, w),),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, w), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(*operands)
    return out[0]


def one_pass_operand(x: jax.Array) -> jax.Array:
    """x rounded to the nearest bf16 value (ties to even), held in fp32:
    the operand rounding of the MXU's one-pass product of fp32 values,
    which a TPU applies to a dot at default precision (the flat kernel's
    mix, the xla backend's).  Integer arithmetic on the bits: compiled for
    the chip, a convert to bf16 and back is dropped as excess precision."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    bits = bits + (0x7FFF + ((bits >> 16) & 1))
    rounded = jax.lax.bitcast_convert_type(bits & -0x10000, jnp.float32)
    return jnp.where(x != x, x, rounded)          # NaN stays NaN


def _make_leaf_kernel(mode: str, mix: bool, f, *, shape: tuple,
                      block: tuple, one_pass: bool):
    """Kernel body over a (n, R, C) leaf view: each slab piece of the
    (n, r, w) block is mixed (``y_k = sum_j M[k, j] x_j``, scalar x slab
    summed in fp32, M's column j a (n, 1, 1) operand), sorted across
    workers by :func:`_sort_slabs` and reduced to its (rows, lanes) piece
    of the output block, which has the leaf's own layout.  ``one_pass``
    rounds X to bf16 first (the wrapper rounds M the same way), as the
    flat kernel's one-pass MXU dot does on the chip.  Rows and lanes past the leaf are computed
    but never written back, so nothing is masked.  ``f=None`` reads the
    trim count from scalar memory, as the flat kernel does."""
    n = shape[0]

    def kernel(*refs):
        refs = list(refs)
        f_val = f if f is not None else refs.pop(0)[0, 0]
        m_ref = refs.pop(0) if mix else None
        x_ref, o_ref = refs

        def slab(carry, row, height, valid_r, lane, width, valid_l):
            x = x_ref[:, pl.ds(row, height), pl.ds(lane, width)]
            x = x.astype(jnp.float32)
            y = x
            if mix:
                xm = one_pass_operand(x) if one_pass else x
                y = m_ref[0] * xm[0][None]
                for j in range(1, n):
                    y = y + m_ref[j] * xm[j][None]
            ys = _sort_slabs([y[k] for k in range(n)])
            o_ref[pl.ds(row, height), pl.ds(lane, width)] = _reduce_ranks(
                ys.__getitem__, f_val, mode, n)
            return carry

        tiling.walk_slabs(pl.program_id(0), pl.program_id(1), shape=shape,
                          block=block, lanes=tiling.slab_lanes(
                              MIXTRIM_SLABS * n, x_ref.dtype),
                          group=2, body=slab, dtype=x_ref.dtype)
    return kernel


def _mixtrim_leaf_call(x, m, f, *, mode: str, block_rows: int | None,
                       interpret: bool, one_pass: bool | None = None):
    """The pallas_call over a (n, R, C) leaf view: (n, r, w) blocks in,
    (r, w) blocks of the (R, C) fp32 aggregate out; M's columns, as
    (n, 1, 1) operands, and a traced ``f`` in scalar memory ride along.

    The mix takes the precision of a default-precision dot on the
    platform it runs on, as the flat kernel's and the xla backend's do, so
    all backends aggregate alike there: ``one_pass`` (None: compiled for
    the chip) rounds both operands by :func:`one_pass_operand`, the MXU's
    one-pass product; the interpreter's dot is exact fp32."""
    n = x.shape[0]
    one_pass = not interpret if one_pass is None else one_pass
    block = tiling.leaf_block(x.shape, block_rows, x.dtype)
    in_specs = [pl.BlockSpec((n, *block), lambda i, j: (0, i, j))]
    operands = [x]
    if m is not None:
        m = m.astype(jnp.float32)
        if one_pass:
            m = one_pass_operand(m)
        in_specs.insert(0, pl.BlockSpec((n, n, 1, 1),
                                        lambda i, j: (0, 0, 0, 0)))
        operands.insert(0, m.T.reshape(n, n, 1, 1))
    static_f = None
    if isinstance(f, int):
        static_f = f
    else:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.insert(0, jnp.asarray(f, jnp.int32).reshape(1, 1))
    return pl.pallas_call(
        _make_leaf_kernel(mode, m is not None, static_f, shape=x.shape,
                          block=block, one_pass=one_pass),
        grid=tiling.leaf_grid(x.shape, block),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(block, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape[1:], jnp.float32),
        interpret=interpret,
    )(*operands)


def _mixtrim_any(x, m, f, *, mode: str, block_d: int | None,
                 interpret: bool):
    """Route by operand rank: a leaf view to the slab kernel, a (n, d)
    stack to the flat kernel."""
    if x.ndim == 3:
        return _mixtrim_leaf_call(x, m, f, mode=mode, block_rows=block_d,
                                  interpret=interpret)
    return _mixtrim_call(x, m, f, mode=mode, block_d=block_d,
                         interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("f", "mode", "block_d", "interpret"))
def mixtrim_pallas(x: jax.Array, m: jax.Array, *, f: int, mode: str = "trim",
                   block_d: int | None = None, interpret: bool = False
                   ) -> jax.Array:
    """Fused (M @ X -> sort -> trim/median) over d tiles.

    Args:
      x: (n, d) worker stack, any n >= 1, any d (block_d a multiple of
        128, or None to pick it from x's shape and dtype).
        Non-power-of-two n runs the padded sentinel sort (see module
        docs).  Or a (n, R, C) leaf view (block_d its rows per grid
        step, or None), reduced slab by slab to an (R, C) aggregate.
      m: (n, n) mixing matrix, or None for plain CWTM/CWMed (the mix dot
        is elided entirely — no identity matmul).
      f: trim count (ignored for mode="med").
      mode: "trim" or "med".
    Returns: (d,) fp32 aggregate; (R, C) for a leaf view.
    """
    return _mixtrim_any(x, m, int(f), mode=mode, block_d=block_d,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("mode", "block_d", "interpret"))
def mixtrim_dyn_pallas(x: jax.Array, m: jax.Array, f: jax.Array, *,
                       mode: str = "trim", block_d: int | None = None,
                       interpret: bool = False) -> jax.Array:
    """Fused mix+trim with a TRACED Byzantine count.

    Same tiling as :func:`mixtrim_pallas` (including the padded sentinel
    sort for non-power-of-two n); ``f`` rides along as a (1, 1) int32
    scalar-memory operand (two dims, so its lane-batched form still lowers
    on a TPU), and trimming goes through a rank mask.  Under
    ``jax.vmap`` (the fleet's lane axis) the pallas batching rule prepends
    a lane grid dimension, so a whole shape bucket still costs one
    compile.
    """
    return _mixtrim_any(x, m, jnp.asarray(f, jnp.int32), mode=mode,
                        block_d=block_d, interpret=interpret)
