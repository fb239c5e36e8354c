"""How the streaming kernels tile the feature dim of a worker stack.

Two widths, decoupled:

* the **grid tile** W: each grid step DMAs one ``(n, W)`` block of the
  stack into VMEM.  A TPU grid step has a fixed cost (pipeline
  bookkeeping and starting one DMA) that a narrow block cannot hide, so
  :func:`pick_block_d` takes the widest W whose buffers fit
  :data:`VMEM_BUDGET`, from the stack's shape and dtype alone;
* the **chunk**: inside the grid step, :func:`walk_chunks` runs the
  kernel body over the block a chunk of lanes at a time, so the body's
  values (the mixed stack, the sort network, one Gram term) stay a few
  dozen vregs whatever W is.

The Gram kernel accumulates :data:`CHUNK`-lane chunks that start at
multiples of CHUNK from column 0 of the stack for every W that is a
multiple of it, so it adds the same terms in the same order as a grid of
CHUNK-wide tiles.  The per-column kernels (mixtrim, combine) take wider
chunks (:func:`chunk_lanes`): their results do not depend on the chunk.
Only the chunk that holds the stack's ragged end is masked, and only in
the last grid step.

A single-device stack reaches the kernels leaf by leaf, each leaf as
the 3-D **leaf view** ``(n, R, C)`` of its own layout (C the minor dim of
its TPU layout, R the product of the others but n: :func:`leaf_shape`).
Its grid walks ``(n, r, w)`` blocks (:func:`pick_leaf_block`): r rows, a
multiple of the dtype's sublane tile or R whole, and w lanes, C whole
unless one tile of rows of it overflows VMEM.  Inside a block
:func:`walk_slabs` hands the body one **slab** piece at a time: each
worker's own ``(rows, lanes)`` tile, the same rows and lanes for every
worker, so a body combines workers with elementwise arithmetic and no
data movement between sublanes.  Ragged blocks along R and C are walked
like ragged chunks: only the last grid step masks.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

#: Lanes of one Gram chunk: the grid tile of the 512-lane kernels before
#: tiles were widened, kept so the accumulation order stays theirs.
CHUNK = 512

#: Gram chunks per loop iteration: independent MXU products the compiler
#: overlaps, summed into the accumulator in column order.  One chunk per
#: iteration pays the product's latency 32 times over (17.2 against 3.0
#: ms for a (4, 78.6 M) fp32 stack on a TPU v5e).
GROUP = 32

#: fp32 values one chunk of the per-column kernels holds (rows x lanes):
#: 32 vregs.  Wider chunks pay the per-chunk cost of the MXU mix fewer
#: times (mixtrim on a (4, 78.6 M) fp32 stack on a TPU v5e: 22.1 ms in
#: 512-lane chunks, 6.7 ms in 4096-lane ones); taller stacks take
#: narrower ones.
CHUNK_VALUES = 32768

#: VMEM one kernel's blocks and chunk working set may take: half of
#: Mosaic's default scoped VMEM limit on a TPU v5e (16 MiB), leaving the
#: compiler room for its own scratch.
VMEM_BUDGET = 8 * 2**20

#: Rows of one fp32 vreg tile, and lanes of every vreg.
SUBLANES = 8
LANES = 128

#: vregs one slab piece's live values may take (inputs, mixed slabs,
#: accumulators): :func:`slab_lanes` narrows the piece as n grows.
SLAB_VREGS = 32


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (the bitonic network height)."""
    return 1 << (n - 1).bit_length()


def sort_height(n: int) -> int:
    """Rows of the bitonic network for n workers: a power of two, and at
    least one full sublane tile (8 rows) so every rotation is whole-tile."""
    return max(SUBLANES, next_pow2(n))


def chunk_lanes(rows: int) -> int:
    """Lanes of one chunk of a per-column kernel over ``rows`` fp32 rows:
    the widest power of two from :data:`CHUNK` to 4096 holding at most
    :data:`CHUNK_VALUES` values (a taller stack keeps CHUNK lanes, the
    tile the kernels ran before tiles were widened: narrower chunks run
    the sort network slower)."""
    lanes = CHUNK
    while lanes < 4096 and 2 * lanes * rows <= CHUNK_VALUES:
        lanes *= 2
    return lanes


def _rows(n: int, itemsize: int) -> int:
    """Rows n occupies in VMEM: padded to the dtype's sublane tile (8 rows
    of fp32, 16 of bf16, 32 of 8-bit)."""
    return _round_up(n, SUBLANES * 4 // itemsize)


def vmem_bytes(n: int, width: int, dtype=jnp.float32) -> int:
    """VMEM a streaming kernel takes for a (n, ``width``) grid tile: the
    double-buffered input block, a double-buffered fp32 output row (the
    widest output of the streaming kernels: mixtrim's and combine's),
    the (rows, n) mixing matrix, and four fp32 values of one chunk of the
    sort network (the mixed stack, its partners, the sorted stack)."""
    itemsize = np.dtype(dtype).itemsize
    rows = sort_height(n)
    x_in = 2 * _rows(n, itemsize) * width * itemsize
    out = 2 * SUBLANES * width * 4
    mix = 2 * rows * _round_up(n, LANES) * 4
    work = 4 * rows * min(chunk_lanes(rows), _round_up(width, LANES)) * 4
    return x_in + out + mix + work


def pick_block_d(n: int, d: int, dtype=jnp.float32) -> int:
    """Grid tile width W for a (n, d) stack of ``dtype``: the widest that
    fits :data:`VMEM_BUDGET` (:func:`vmem_bytes`), never wider than d
    rounded up to 128 lanes.  A W that splits d over several grid steps is
    a multiple of :data:`CHUNK`, so the Gram chunks keep their columns;
    below one chunk it is a multiple of 128, and at least 128."""
    cap = _round_up(max(d, 1), LANES)
    if vmem_bytes(n, cap, dtype) <= VMEM_BUDGET:
        return cap
    step = CHUNK if vmem_bytes(n, CHUNK, dtype) <= VMEM_BUDGET else LANES
    w = step
    lo, hi = 1, cap // step            # widest multiple of step that fits
    while lo <= hi:
        mid = (lo + hi) // 2
        if vmem_bytes(n, mid * step, dtype) <= VMEM_BUDGET:
            w, lo = mid * step, mid + 1
        else:
            hi = mid - 1
    return w


def block_width(d: int, block_d: Optional[int], n: int, dtype) -> int:
    """The grid tile a kernel runs: ``block_d`` as given, else the picked
    one; never wider than d (a tile of d itself is the whole stack)."""
    if block_d is None:
        block_d = pick_block_d(n, d, dtype)
    return min(block_d, d)


def grid_steps(d: int, width: int) -> int:
    """Grid steps a d-wide stream takes in ``width``-wide tiles."""
    return pl.cdiv(d, width)


#: body(carry, offset, width, valid) -> carry: one chunk of the grid tile
#: at lane ``offset`` (traced or static), ``width`` lanes; ``valid`` is
#: None for a whole chunk, else how many leading columns lie inside the
#: stack (the rest must not reach an accumulator).
ChunkBody = Callable[[object, object, int, Optional[int]], object]


def _run(body: ChunkBody, carry, chunk: int, n_full: int, rest,
         group: int):
    """``n_full`` whole chunks, ``group`` to a loop iteration and the
    remainder inline (a loop of single chunks would pay each one's
    latency again), then the ``rest`` = (width, valid) chunk."""
    def run(g, c):
        for u in range(group):
            off = (g * group + u) * chunk
            if not isinstance(off, int):
                off = pl.multiple_of(off, chunk)
            c = body(c, off, chunk, None)
        return c

    iters = n_full // group
    if iters == 1:
        carry = run(0, carry)
    elif iters > 1:
        carry = jax.lax.fori_loop(0, iters, run, carry)
    for u in range(iters * group, n_full):
        carry = body(carry, u * chunk, chunk, None)
    if rest is not None:
        width, valid = rest
        carry = body(carry, n_full * chunk, width, valid)
    return carry


def walk_chunks(step, *, d: int, width: int, chunk: int, body: ChunkBody,
                group: int = 1, init=None,
                finish: Callable = lambda carry: None) -> None:
    """Run ``body`` over grid step ``step``'s (·, ``width``) tile of a
    d-wide stream, ``chunk`` lanes at a time in column order (the last
    chunk of a tile that is not a multiple of it is narrower), then
    ``finish`` on the carry.

    Every step but the last walks the whole tile unmasked.  The last one
    walks only the chunks that hold columns below d, and the chunk that
    ends at d's ragged edge is handed its count of valid columns, so no
    other chunk pays for a mask."""
    chunk = min(chunk, width)
    per_step = width // chunk
    tail = width - per_step * chunk
    full_rest = (tail, None) if tail else None
    steps = grid_steps(d, width)
    last = d - (steps - 1) * width
    n_last = min(last // chunk, per_step)
    rem = last - n_last * chunk
    last_rest = None
    if rem:
        w = chunk if n_last < per_step else tail
        last_rest = (w, rem if rem < w else None)

    def walk(n_full, rest):
        finish(_run(body, init, chunk, n_full, rest, group))

    if steps == 1:
        walk(n_last, last_rest)
    elif (n_last, last_rest) == (per_step, full_rest):
        walk(per_step, full_rest)
    else:
        pl.when(step < steps - 1)(lambda: walk(per_step, full_rest))
        pl.when(step == steps - 1)(lambda: walk(n_last, last_rest))


def valid_columns(x: jax.Array, valid: Optional[int]) -> jax.Array:
    """x with its columns from ``valid`` on zeroed (a select: whatever the
    ragged tile held past the stack's end never reaches a sum)."""
    if valid is None:
        return x
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where(col < valid, x, 0.0)


# ---------------------------------------------------------------------------
# The leaf view: (n, R, C) blocks walked slab by slab.
# ---------------------------------------------------------------------------

def minor_swapped(shape: tuple) -> bool:
    """Whether the TPU's default layout of a worker-stacked leaf of
    ``shape`` = (n, ..., a, b) keeps its last two dims swapped, a the
    minor one: the layout it picks when a tiles into fewer padded elements
    ((8, 128) tiles: b padded to 8 rows, a to 128 lanes) than b does; a
    tie keeps row-major.  So (960, 320), (2560, 960) and (49152, 960)
    leaves are held a-minor, (960, 2560) and (512, 512) ones row-major."""
    if len(shape) < 3:
        return False
    a, b = shape[-2:]
    return (_round_up(b, SUBLANES) * _round_up(a, LANES)
            < _round_up(a, SUBLANES) * _round_up(b, LANES))


def leaf_shape(shape: tuple) -> tuple[int, int, int]:
    """(n, R, C) leaf view of a worker-stacked leaf of ``shape``, in the
    order its TPU layout holds it: C the minor dim (the last, or the
    second-to-last where :func:`minor_swapped`), R the product of the
    others but n (1 for a 2-D leaf; a 1-D leaf is (n, 1, 1)).  The view
    is a bitcast of the leaf's tiled layout when the dim above C is a
    multiple of the sublane tile (8 rows)."""
    n = shape[0]
    if len(shape) == 1:
        return n, 1, 1
    minor = -2 if minor_swapped(shape) else -1
    return n, int(np.prod(shape[1:], dtype=np.int64)) // shape[minor], \
        shape[minor]


def row_tile(dtype) -> int:
    """Rows of one vreg tile of ``dtype`` (8 of fp32, 16 of bf16): the
    height of a slab and the multiple a split row block keeps."""
    return SUBLANES * 4 // np.dtype(dtype).itemsize


def slab_lanes(slabs: int, dtype=jnp.float32) -> int:
    """Lanes of one slab piece whose body holds ``slabs`` live worker slabs
    (inputs, mixed slabs, accumulators): 512 at most, narrower as they
    grow, so the body stays near :data:`SLAB_VREGS`."""
    vregs = slabs * row_tile(dtype) // SUBLANES
    return LANES * max(1, min(4, SLAB_VREGS // vregs))


def gram_lanes(n: int, dtype=jnp.float32) -> int:
    """Slab lanes of the leaf Gram: n inputs and n x n accumulators."""
    return slab_lanes(n + n * n, dtype)


def gram_vregs(n: int, dtype=jnp.float32) -> int:
    """vregs the leaf Gram's body keeps live at its narrowest slab piece
    (a row tile by 128 lanes): n input slabs and n x n fp32 accumulators,
    each widened to fp32."""
    return (n + n * n) * row_tile(dtype) // SUBLANES


def leaf_view_fits(n: int, dtype=jnp.float32) -> bool:
    """Whether a stack of n workers of ``dtype`` goes to the kernels as
    leaf views: while the leaf Gram's body fits :data:`SLAB_VREGS`
    (n <= 5 of fp32, n <= 3 of bf16).  A taller stack would spill on every
    row tile (n=17 keeps 306 vregs live, of the core's 64), so its leaves
    go as flat (n, d_i) views to the MXU forms."""
    return gram_vregs(n, dtype) <= SLAB_VREGS


def leaf_vmem_bytes(n: int, rows: int, width: int, dtype=jnp.float32) -> int:
    """VMEM a leaf-view kernel takes for a (n, ``rows``, ``width``) block:
    the double-buffered input block and fp32 output block (mixtrim's and
    combine's; rows and lanes padded to whole vreg tiles) and the Gram's
    (n, n) slab accumulators."""
    itemsize = np.dtype(dtype).itemsize
    lanes = _round_up(width, LANES)
    x_in = 2 * n * _round_up(rows, row_tile(dtype)) * lanes * itemsize
    out = 2 * _round_up(rows, SUBLANES) * lanes * 4
    acc = n * n * row_tile(dtype) * min(gram_lanes(n, dtype), lanes) * 4
    return x_in + out + acc


def _widest(limit: int, step: int, total: int, fits) -> int:
    """The widest multiple of ``step`` up to ``limit`` that ``fits``,
    moved down to a divisor of ``total`` when one lies within half of it
    (an even split has no ragged last block); ``step`` if none fits."""
    w = step
    lo, hi = 1, limit // step
    while lo <= hi:
        mid = (lo + hi) // 2
        if fits(mid * step):
            w, lo = mid * step, mid + 1
        else:
            hi = mid - 1
    for cand in range(w, w // 2, -step):
        if total % cand == 0:
            return cand
    return w


def pick_leaf_block(n: int, rows: int, cols: int,
                    dtype=jnp.float32) -> tuple[int, int]:
    """(r, w) block of a (n, ``rows``, ``cols``) leaf view: w = cols whole
    unless one row tile of it overflows :data:`VMEM_BUDGET`, then a
    multiple of 128; r the tallest that fits beside it, R whole or a
    multiple of the row tile."""
    tile = row_tile(dtype)
    h = min(rows, tile)

    def fits(r, w):
        return leaf_vmem_bytes(n, r, w, dtype) <= VMEM_BUDGET

    w = cols
    if not fits(h, cols):
        w = _widest(_round_up(cols, LANES) - LANES, LANES, cols,
                    lambda v: fits(h, v))
    if fits(rows, w) or rows <= tile:
        return rows, w
    return _widest(rows, tile, rows, lambda v: fits(v, w)), w


def leaf_grid(x_shape: tuple, block: tuple) -> tuple[int, int]:
    """Grid (row blocks, lane blocks) of a leaf view in ``block``s."""
    return grid_steps(x_shape[1], block[0]), grid_steps(x_shape[2], block[1])


def leaf_block(x_shape: tuple, block_rows: Optional[int],
               dtype) -> tuple[int, int]:
    """The (r, w) block a leaf-view kernel runs: ``block_rows`` rows as
    given (never more than R), else the picked block."""
    n, rows, cols = x_shape
    r, w = pick_leaf_block(n, rows, cols, dtype)
    if block_rows is not None:
        r = min(block_rows, rows)
    return r, w


#: body(carry, row, height, valid_rows, lane, width, valid_lanes) ->
#: carry: one slab piece of the block at (row, lane), height x width;
#: a valid count is None for a whole piece, else how many leading rows
#: (lanes) lie inside the leaf.
SlabBody = Callable[..., object]


def walk_slabs(row_step, lane_step, *, shape: tuple, block: tuple,
               lanes: int, group: int, body: SlabBody,
               init: Callable = lambda width: None,
               finish: Callable = lambda carry, lane, width: None,
               dtype=jnp.float32) -> None:
    """Run ``body`` over grid step (``row_step``, ``lane_step``)'s (r, w)
    block of a (n, R, C) leaf view, one slab piece at a time: lanes in
    ``lanes``-wide pieces (outer), rows a vreg tile at a time (inner,
    ``group`` tiles to a loop iteration: a loop of single tiles pays its
    iteration's cost on a few vregs of work), the carry started by
    ``init(width)`` and handed to ``finish(carry, lane, width)`` after each
    lane piece's rows.  Only the last grid step along an axis walks its
    ragged end, and only the piece that holds it is told its valid count
    (:func:`walk_chunks` along each axis)."""
    _, rows, cols = shape
    r, w = block

    def lane_piece(carry, lane, width, valid_lanes):
        def row_piece(acc, row, height, valid_rows):
            return body(acc, row, height, valid_rows, lane, width,
                        valid_lanes)

        walk_chunks(row_step, d=rows, width=r, chunk=row_tile(dtype),
                    body=row_piece, group=group, init=init(width),
                    finish=lambda acc: finish(acc, lane, width))
        return carry

    walk_chunks(lane_step, d=cols, width=w, chunk=lanes, body=lane_piece)


def valid_rows(x: jax.Array, valid: Optional[int]) -> jax.Array:
    """x with its rows (second-to-last dim) from ``valid`` on zeroed."""
    if valid is None:
        return x
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 2)
    return jnp.where(row < valid, x, 0.0)
