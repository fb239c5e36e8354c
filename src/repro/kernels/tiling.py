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
