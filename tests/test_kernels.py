"""Per-kernel tests vs the pure-jnp oracles, with the Pallas body executed
in interpret mode (CPU).

Two tiers: allclose shape/dtype sweeps, and BIT-EXACT agreement of the
gram / mixtrim / combine primitives with their refs (the refs share the
kernels' dot_general forms, so interpret mode reproduces them exactly —
the contract the backend-parity acceptance rests on)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import tiling
from repro.kernels.combine import combine, combine_ref
from repro.kernels.gram import gram, gram_batched, gram_batched_ref, gram_ref
from repro.kernels.mixtrim import (
    mixtrim, mixtrim_dyn, mixtrim_dyn_ref, mixtrim_ref,
)

try:                      # optional dev dep; property tests skip cleanly
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False


@pytest.mark.parametrize("n", [8, 16, 32])
@pytest.mark.parametrize("d", [64, 100, 512, 777, 2048])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_sweep(n, d, dtype):
    x = jax.random.normal(jax.random.PRNGKey(n * d), (n, d), dtype=dtype)
    got = np.asarray(gram(x, block_d=256))
    want = np.asarray(gram_ref(x))
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * d)


@pytest.mark.parametrize("n", [8, 16, 32])
@pytest.mark.parametrize("d", [64, 100, 640])
@pytest.mark.parametrize("mode", ["trim", "med"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mixtrim_sweep(n, d, mode, dtype):
    key = jax.random.PRNGKey(n + d)
    x = jax.random.normal(key, (n, d), dtype=dtype)
    m = jnp.eye(n, dtype=jnp.float32) * 0.6 + jnp.ones((n, n)) * (0.4 / n)
    for f in (0, 1, n // 2 - 1):
        got = np.asarray(mixtrim(x, m, f=f, mode=mode, block_d=128))
        want = np.asarray(mixtrim_ref(x, m, f, mode))
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


if _HAVE_HYPOTHESIS:
    @given(st.integers(0, 100_000), st.sampled_from([8, 16]),
           st.integers(1, 700))
    @settings(max_examples=25, deadline=None)
    def test_mixtrim_hypothesis(seed, n, d):
        """Random mixing matrices + ragged d (padding path)."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        x = jax.random.normal(k1, (n, d))
        m = jax.nn.softmax(jax.random.normal(k2, (n, n)), axis=-1)
        f = n // 4
        got = np.asarray(mixtrim(x, m, f=f, mode="trim", block_d=256))
        want = np.asarray(mixtrim_ref(x, m, f, "trim"))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_mixtrim_nonpow2_runs_padded_kernel():
    """n=17 (paper scale) runs the fused kernel through the sentinel-padded
    bitonic network — no jnp-oracle fallback — and matches the oracle."""
    x = jax.random.normal(jax.random.PRNGKey(0), (17, 100))
    m = jnp.eye(17)
    got = np.asarray(mixtrim(x, m, f=4, mode="trim"))
    want = np.asarray(mixtrim_ref(x, m, 4, "trim"))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_gram_is_psd_and_symmetric():
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 300))
    g = np.asarray(gram(x))
    np.testing.assert_allclose(g, g.T, rtol=1e-5)
    w = np.linalg.eigvalsh(g)
    assert w.min() > -1e-3


# ---------------------------------------------------------------------------
# Bit-exactness: interpret-mode kernels == their jnp refs, to the last ulp.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d", [128, 256, 512])
def test_gram_bitexact_vs_ref(dtype, d):
    """One tile, no padding: the kernel contraction is the ref's
    dot_general verbatim, so interpret mode is bit-exact."""
    x = jax.random.normal(jax.random.PRNGKey(7), (16, d), dtype=dtype)
    got = np.asarray(gram(x, block_d=d))
    np.testing.assert_array_equal(got, np.asarray(gram_ref(x)))


@pytest.mark.parametrize("d,block_d", [(512, 128), (384, 512), (100, 256)])
def test_gram_blocked_accumulation_tight(d, block_d):
    """Tiling or zero-padding the CONTRACTION dim reorders the fp32 sum;
    agreement must still be fp32-dot tight (bit-exactness only holds for a
    single unpadded tile)."""
    x = jax.random.normal(jax.random.PRNGKey(7), (16, d))
    got = np.asarray(gram(x, block_d=block_d))
    np.testing.assert_allclose(got, np.asarray(gram_ref(x)),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("mode", ["trim", "med"])
@pytest.mark.parametrize("d,block_d", [(640, 128), (100, 256)])
def test_mixtrim_bitexact_vs_ref(mode, d, block_d):
    x = jax.random.normal(jax.random.PRNGKey(8), (16, d))
    m = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(9), (16, 16)),
                       axis=-1)
    got = np.asarray(mixtrim(x, m, f=3, mode=mode, block_d=block_d))
    np.testing.assert_array_equal(got, np.asarray(mixtrim_ref(x, m, 3, mode)))


def test_combine_bitexact_vs_ref():
    x = jax.random.normal(jax.random.PRNGKey(10), (16, 700))
    c = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(11), (16,)))
    got = np.asarray(combine(x, c, block_d=256))
    np.testing.assert_array_equal(got, np.asarray(combine_ref(x, c)))


def test_mixtrim_dyn_bitexact_vs_ref():
    x = jax.random.normal(jax.random.PRNGKey(12), (16, 384))
    m = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(13), (16, 16)),
                       axis=-1)
    for f in (0, 1, 5, 7):
        got = np.asarray(mixtrim_dyn(x, m, jnp.int32(f), block_d=128))
        want = np.asarray(mixtrim_dyn_ref(x, m, jnp.int32(f)))
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Wide grid tiles: several 512-lane chunks per tile, a ragged last chunk.
# ---------------------------------------------------------------------------

#: d = 1300 in tiles of 1024 (two grid steps, the last holding one ragged
#: chunk) or of the picked width (one 1408-wide step: two whole chunks and
#: a ragged 384-lane tail).
WIDE_D = 1300
WIDE_TILES = [None, 1024]


def _wide_stack(n):
    x = jax.random.normal(jax.random.PRNGKey(30 + n), (n, WIDE_D))
    m = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(31), (n, n)),
                       axis=-1)
    return x, m, max(1, (n - 1) // 4)


@pytest.mark.parametrize("n", [4, 17])
@pytest.mark.parametrize("block_d", WIDE_TILES)
def test_wide_tile_gram_keeps_chunk_order(n, block_d):
    """A wide tile sums the same 512-lane chunks in the same order as a
    grid of 512-lane tiles, so the Gram is bit for bit the narrow one;
    every lane of the batched kernel is bit for bit the solo kernel."""
    assert tiling.CHUNK == 512
    x, _, _ = _wide_stack(n)
    got = np.asarray(gram(x, block_d=block_d))
    np.testing.assert_array_equal(got, np.asarray(gram(x, block_d=512)))
    np.testing.assert_allclose(got, np.asarray(gram_ref(x)),
                               rtol=1e-4, atol=1e-3)
    lanes = jnp.stack([x, 2 * x, -x])
    got_b = np.asarray(gram_batched(lanes, block_d=block_d))
    np.testing.assert_array_equal(
        got_b, np.asarray(gram_batched(lanes, block_d=512)))
    np.testing.assert_array_equal(got_b[0], got)


@pytest.mark.parametrize("n", [4, 17])
@pytest.mark.parametrize("block_d", WIDE_TILES)
def test_wide_tile_combine_bitexact(n, block_d):
    x, m, _ = _wide_stack(n)
    got = np.asarray(combine(x, m[0], block_d=block_d))
    np.testing.assert_array_equal(got, np.asarray(combine_ref(x, m[0])))


@pytest.mark.parametrize("n", [4, 17])
@pytest.mark.parametrize("block_d", WIDE_TILES)
@pytest.mark.parametrize("mode", ["trim", "med"])
@pytest.mark.parametrize("mix", [True, False])
def test_wide_tile_mixtrim_bitexact(n, block_d, mode, mix):
    """Static and dynamic f, with and without the mix dot: per-column
    math, so any tile is bit for bit the oracle."""
    x, m, f = _wide_stack(n)
    mm = m if mix else None
    got = np.asarray(mixtrim(x, mm, f=f, mode=mode, block_d=block_d))
    np.testing.assert_array_equal(got,
                                  np.asarray(mixtrim_ref(x, mm, f, mode)))
    got_dyn = np.asarray(mixtrim_dyn(x, mm, jnp.int32(f), mode=mode,
                                     block_d=block_d))
    np.testing.assert_array_equal(
        got_dyn, np.asarray(mixtrim_dyn_ref(x, mm, jnp.int32(f), mode)))


# ---------------------------------------------------------------------------
# Streamed combine: sweeps + bf16 transport contract.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [8, 16, 32])
@pytest.mark.parametrize("d", [64, 100, 777])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_combine_sweep(n, d, dtype):
    x = jax.random.normal(jax.random.PRNGKey(n + d), (n, d), dtype=dtype)
    c = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(d), (n,)))
    got = np.asarray(combine(x, c, block_d=128))
    want = np.asarray(combine_ref(x, c))
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    assert got.dtype == np.float32      # fp32 accumulate regardless of input


# ---------------------------------------------------------------------------
# Lane-batched gram: one launch per fleet shape bucket.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b", [1, 3, 8])
@pytest.mark.parametrize("d", [100, 512])
def test_gram_batched_matches_per_lane(b, d):
    x = jax.random.normal(jax.random.PRNGKey(b * d), (b, 16, d))
    got = np.asarray(gram_batched(x, block_d=256))
    np.testing.assert_allclose(got, np.asarray(gram_batched_ref(x)),
                               rtol=1e-4, atol=1e-3)
    # every lane is BIT-FOR-BIT the solo blocked kernel on its own slice
    # (identical tiling on both sides, so no sum-reorder caveat applies)
    for k in range(b):
        np.testing.assert_array_equal(
            got[k], np.asarray(gram(x[k], block_d=256)))


# ---------------------------------------------------------------------------
# Dynamic-f mixtrim: one compile serves every Byzantine budget.
# ---------------------------------------------------------------------------

def test_mixtrim_dyn_matches_static_across_f_one_compile():
    """The rank-mask kernel must agree with the static-slice kernel for all
    f while tracing exactly once (the fleet shape-bucket contract)."""
    x = jax.random.normal(jax.random.PRNGKey(14), (16, 256))
    m = jnp.eye(16, dtype=jnp.float32)
    traces = []

    @jax.jit
    def agg(x, m, f):
        traces.append(1)
        return mixtrim_dyn(x, m, f, block_d=128)

    for f in (0, 1, 3, 5, 7):
        got = np.asarray(agg(x, m, jnp.int32(f)))
        want = np.asarray(mixtrim(x, m, f=f, mode="trim", block_d=128))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert len(traces) == 1, f"expected one trace, got {len(traces)}"


def test_mixtrim_dyn_vmap_lane_batch():
    """vmap over (x, f) — the fleet lane axis — stays correct per lane."""
    xs = jax.random.normal(jax.random.PRNGKey(15), (4, 8, 128))
    m = jnp.eye(8, dtype=jnp.float32)
    fs = jnp.asarray([0, 1, 2, 3], jnp.int32)
    out = jax.vmap(lambda x, f: mixtrim_dyn(x, m, f, block_d=128))(xs, fs)
    for k in range(4):
        np.testing.assert_allclose(
            np.asarray(out[k]),
            np.asarray(mixtrim_dyn_ref(xs[k], m, fs[k])),
            rtol=1e-6, atol=1e-6)


def test_mixtrim_dyn_nonpow2_runs_padded_kernel():
    """n=17 through the dyn rank-mask kernel: the sentinel pad rows sort
    above every real value, so their ranks never enter the keep mask."""
    x = jax.random.normal(jax.random.PRNGKey(16), (17, 100))
    m = jnp.eye(17)
    got = np.asarray(mixtrim_dyn(x, m, jnp.int32(4)))
    want = np.asarray(mixtrim_dyn_ref(x, m, jnp.int32(4)))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Padded sentinel sort: non-power-of-two n runs the fused kernel.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [3, 5, 17])
@pytest.mark.parametrize("mode", ["trim", "med"])
def test_mixtrim_padded_sort_vs_oracle(n, mode):
    """The federated worker counts the pow2 network used to reject (n=17 is
    the paper's own scale): f=0 and f one below breakdown, with and without
    the mix dot, static and dynamic f — all through the padded kernel."""
    x = jax.random.normal(jax.random.PRNGKey(n), (n, 130))
    m = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(n + 1), (n, n)),
                       axis=-1)
    for f in (0, max(0, (n - 1) // 2)):
        for mm in (m, None):
            got = np.asarray(mixtrim(x, mm, f=f, mode=mode, block_d=128))
            want = np.asarray(mixtrim_ref(x, mm, f, mode))
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6,
                                       err_msg=f"n={n} f={f} mode={mode}")
            got_dyn = np.asarray(mixtrim_dyn(x, mm, jnp.int32(f), mode=mode,
                                             block_d=128))
            want_dyn = np.asarray(mixtrim_dyn_ref(x, mm, jnp.int32(f), mode))
            np.testing.assert_allclose(got_dyn, want_dyn, rtol=1e-6,
                                       atol=1e-6,
                                       err_msg=f"dyn n={n} f={f} mode={mode}")


def test_mixtrim_padded_sort_negative_and_tied_values():
    """Sentinels must dominate NEGATIVE values too (fp32 max, not |max|),
    and exact ties among real rows must not disturb the trim ranks."""
    x = jnp.asarray(np.array([[-5.0, -1.0], [-5.0, 3.0], [2.0, -1.0],
                              [2.0, 3.0], [9.0, -7.0]]), jnp.float32)
    for f in (0, 1, 2):
        got = np.asarray(mixtrim(x, None, f=f, mode="trim", block_d=128))
        want = np.asarray(mixtrim_ref(x, None, f, "trim"))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    got = np.asarray(mixtrim(x, None, f=0, mode="med", block_d=128))
    np.testing.assert_allclose(got, np.median(np.asarray(x), axis=0),
                               rtol=1e-6, atol=1e-6)


def test_mixtrim_dyn_padded_vmap_lane_batch():
    """Non-pow2 n under the fleet's lane vmap: the padded kernel batches
    exactly like the pow2 kernel (lane grid dim prepended)."""
    xs = jax.random.normal(jax.random.PRNGKey(22), (3, 5, 128))
    m = jnp.eye(5, dtype=jnp.float32)
    fs = jnp.asarray([0, 1, 2], jnp.int32)
    out = jax.vmap(lambda x, f: mixtrim_dyn(x, m, f, block_d=128))(xs, fs)
    for k in range(3):
        np.testing.assert_allclose(
            np.asarray(out[k]),
            np.asarray(mixtrim_dyn_ref(xs[k], m, fs[k])),
            rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Edge cases: trivial trims, medians at both parities, sub-block d.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["trim", "med"])
def test_mixtrim_no_mix_elides_the_dot(mode):
    """m=None (plain CWTM/CWMed): no identity matmul — the kernel sorts x
    directly and must match both the m=None ref and the explicit-identity
    call bit for bit."""
    x = jax.random.normal(jax.random.PRNGKey(21), (16, 256))
    got = np.asarray(mixtrim(x, None, f=3, mode=mode, block_d=128))
    np.testing.assert_array_equal(got,
                                  np.asarray(mixtrim_ref(x, None, 3, mode)))
    eye = jnp.eye(16, dtype=jnp.float32)
    np.testing.assert_array_equal(
        got, np.asarray(mixtrim(x, eye, f=3, mode=mode, block_d=128)))
    got_dyn = np.asarray(mixtrim_dyn(x, None, jnp.int32(3), mode=mode,
                                     block_d=128))
    np.testing.assert_array_equal(
        got_dyn, np.asarray(mixtrim_dyn_ref(x, None, jnp.int32(3), mode)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mixtrim_f0_is_mixed_mean(dtype):
    x = jax.random.normal(jax.random.PRNGKey(17), (8, 96), dtype=dtype)
    m = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(18), (8, 8)),
                       axis=-1)
    got = np.asarray(mixtrim(x, m, f=0, mode="trim", block_d=128))
    want = np.asarray(m @ x.astype(jnp.float32)).mean(axis=0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [15, 16])
def test_mixtrim_med_even_and_odd_n(n):
    """Median parity: even n averages the two middles (kernel for pow2 n,
    oracle for odd n — both against numpy's median)."""
    x = jax.random.normal(jax.random.PRNGKey(n), (n, 60))
    m = jnp.eye(n, dtype=jnp.float32)
    got = np.asarray(mixtrim(x, m, f=0, mode="med", block_d=128))
    np.testing.assert_allclose(got, np.median(np.asarray(x), axis=0),
                               rtol=1e-6, atol=1e-6)


def test_kernels_sub_block_d():
    """d far below one block: pure padding tail must be exact."""
    x = jax.random.normal(jax.random.PRNGKey(19), (16, 7))
    c = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(20), (16,)))
    m = jnp.eye(16, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(gram(x)),
                                  np.asarray(gram_ref(x)))
    np.testing.assert_allclose(np.asarray(combine(x, c)),
                               np.asarray(combine_ref(x, c)),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(mixtrim(x, m, f=2, mode="trim")),
        np.asarray(mixtrim_ref(x, m, 2, "trim")), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Leaf views: a (n, R, C) leaf read in its own layout, slab by slab.
# ---------------------------------------------------------------------------

#: Rows R of a leaf view with the rows per grid step they run in: one row
#: (a 2-D leaf), R a multiple of the row block, and a ragged last block.
LEAF_ROWS = {"one": (1, None), "even": (16, 8), "ragged": (20, 8)}
#: Minor dims C: SmolLM's k/v width and d_model (not multiples of 128) and
#: a multiple of 128.
LEAF_COLS = [320, 960, 256]


def _leaf_stack(n, rows, cols, dtype=jnp.float32):
    x = jax.random.normal(jax.random.PRNGKey(n * rows + cols),
                          (n, rows, cols), dtype)
    m = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(cols), (n, n)),
                       axis=-1)
    return x, m, max(1, (n - 1) // 4)


@pytest.mark.parametrize("n", [4, 17])
@pytest.mark.parametrize("cols", LEAF_COLS)
@pytest.mark.parametrize("rows", sorted(LEAF_ROWS))
def test_leaf_view_gram_matches_ref(n, cols, rows):
    """The slab Gram of a leaf view equals the oracle's Gram of the same
    values to fp32 rounding, whatever its row blocks."""
    r, block = LEAF_ROWS[rows]
    x, _, _ = _leaf_stack(n, r, cols)
    got = np.asarray(gram(x, block_d=block))
    want = np.asarray(gram_ref(x))
    # fp32 rounding of sums of d products: relative to the diagonal's scale
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6 * scale)
    np.testing.assert_allclose(got, np.asarray(gram(x.reshape(n, -1))),
                               rtol=1e-5, atol=1e-6 * scale)


@pytest.mark.parametrize("n,cols", [(4, c) for c in LEAF_COLS] + [(17, 320)])
@pytest.mark.parametrize("rows", sorted(LEAF_ROWS))
def test_leaf_view_mixtrim_matches_ref(n, cols, rows):
    """Trim and median of a leaf view, static and dynamic f: without the
    mix bit for bit the oracles (a sort of whole slabs reorders nothing
    but ranks), with it within fp32 rounding of the oracle's dot; the
    aggregate keeps the leaf's (R, C) shape."""
    r, block = LEAF_ROWS[rows]
    x, m, f = _leaf_stack(n, r, cols)
    for mode in ("trim", "med"):
        for mm in (m, None):
            got = np.asarray(mixtrim(x, mm, f=f, mode=mode, block_d=block))
            want = np.asarray(mixtrim_ref(x, mm, f, mode))
            assert got.shape == (r, cols)
            got_dyn = np.asarray(mixtrim_dyn(x, mm, jnp.int32(f), mode=mode,
                                             block_d=block))
            want_dyn = np.asarray(mixtrim_dyn_ref(x, mm, jnp.int32(f), mode))
            if mm is None:
                np.testing.assert_array_equal(got, want)
                np.testing.assert_array_equal(got_dyn, want_dyn)
            else:
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
                np.testing.assert_allclose(got_dyn, want_dyn, rtol=1e-5,
                                           atol=1e-5)


@pytest.mark.parametrize("n", [4, 17])
def test_leaf_view_mixtrim_dyn_vmap_lane_batch(n):
    """The fleet's lane vmap over leaf views: each lane's dynamic-f
    aggregate is its solo oracle's."""
    xs = jax.random.normal(jax.random.PRNGKey(40 + n), (3, n, 12, 320))
    m = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(41), (n, n)),
                       axis=-1)
    fs = jnp.asarray([0, 1, (n - 1) // 2], jnp.int32)
    out = jax.vmap(lambda x, f: mixtrim_dyn(x, m, f, block_d=8))(xs, fs)
    for k in range(3):
        np.testing.assert_allclose(
            np.asarray(out[k]), np.asarray(mixtrim_dyn_ref(xs[k], m, fs[k])),
            rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [4, 17])
@pytest.mark.parametrize("rows", sorted(LEAF_ROWS))
def test_leaf_view_combine_matches_ref(n, rows):
    r, block = LEAF_ROWS[rows]
    x, m, _ = _leaf_stack(n, r, 960)
    got = np.asarray(combine(x, m[0], block_d=block))
    assert got.shape == (r, 960)
    np.testing.assert_allclose(got, np.asarray(combine_ref(x, m[0])),
                               rtol=1e-5, atol=1e-5)


def test_leaf_view_splits_a_wide_minor_dim():
    """A leaf whose row tile overflows VMEM at its full width is walked in
    lane blocks too (the ragged last one masked in the Gram)."""
    n, r, c = 17, 9, 8000
    assert tiling.pick_leaf_block(n, r, c)[1] < c
    x, _, f = _leaf_stack(n, r, c)
    want = np.asarray(gram_ref(x))
    np.testing.assert_allclose(np.asarray(gram(x)), want, rtol=1e-5,
                               atol=1e-6 * np.abs(want).max())
    np.testing.assert_array_equal(np.asarray(mixtrim(x, None, f=f)),
                                  np.asarray(mixtrim_ref(x, None, f)))


@pytest.mark.parametrize("n", [4, 17])
def test_sort_pairs_sort_every_order(n):
    """The pruned odd-even merge network sorts n values for every
    permutation of distinct ranks it is tried on."""
    from repro.kernels.mixtrim.kernel import sort_pairs
    rng = np.random.default_rng(n)
    for _ in range(200):
        v = list(rng.permutation(n))
        for a, b in sort_pairs(n):
            v[a], v[b] = min(v[a], v[b]), max(v[a], v[b])
        assert v == sorted(v)


def test_tall_leaf_view_streams_flat():
    """Past ``tiling.leaf_view_fits`` (the leaf Gram's live slabs outgrow
    the vreg budget) the single-device stack goes to the kernels as flat
    (n, d_i) views, and aggregates as the leaf views do below it."""
    from repro.kernels import dispatch as kdispatch
    assert tiling.leaf_view_fits(4) and tiling.leaf_view_fits(5)
    assert not tiling.leaf_view_fits(6) and not tiling.leaf_view_fits(17)
    assert not tiling.leaf_view_fits(4, jnp.bfloat16)
    assert tiling.gram_vregs(5) <= tiling.SLAB_VREGS < tiling.gram_vregs(6)
    for n, view in ((5, 3), (6, 2), (17, 2)):
        x, _, f = _leaf_stack(n, 3, 50)
        segs, layout = kdispatch.split_worker_stack({"w": x})
        assert segs[0].ndim == view
        got = kdispatch.unflatten_aggregate(
            [mixtrim(s, None, f=f) for s in segs], layout)["w"]
        assert got.shape == (3, 50)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(mixtrim_ref(x, None, f)))


def test_round_bf16_matches_the_conversion():
    """The leaf mixtrim's on-chip operand rounding is bf16's round to
    nearest even, specials included."""
    from repro.kernels.mixtrim.kernel import one_pass_operand
    rng = np.random.default_rng(5)
    x = np.concatenate([rng.normal(size=4096) * 10.0 ** rng.integers(
        -30, 30, 4096), [np.nan, np.inf, -np.inf, 0.0, -0.0, 1 / 3]])
    x = jnp.asarray(x, jnp.float32)
    want = np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(one_pass_operand(x)), want)
    assert float(one_pass_operand(jnp.float32(1 / 3))) == 0.333984375


@pytest.mark.parametrize("mode", ["trim", "med"])
@pytest.mark.parametrize("n,rows,cols", [(4, 20, 320), (5, 9, 960)])
def test_leaf_mixtrim_one_pass_matches_bf16_operand_oracle(n, rows, cols,
                                                           mode):
    """The mix the chip runs (both operands rounded to bf16, products
    summed in fp32), exercised in the interpreter: it matches the oracle
    fed bf16-rounded M and X, and not the exact fp32 oracle, from which
    M's rounding alone (1/3 -> 0.333984375) moves it."""
    from repro.kernels.mixtrim.kernel import (_mixtrim_leaf_call,
                                              one_pass_operand)
    x, _, f = _leaf_stack(n, rows, cols)
    m = jnp.full((n, n), 1.0 / (n - f), jnp.float32)
    m = m * (jnp.arange(n)[None, :] < n - f)
    got = np.asarray(_mixtrim_leaf_call(x, m, f, mode=mode, block_rows=None,
                                        interpret=True, one_pass=True))
    want = mixtrim_ref(one_pass_operand(x), one_pass_operand(m), f, mode)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-6, atol=1e-6)
    exact = np.asarray(mixtrim_ref(x, m, f, mode))
    assert np.abs(got - exact).max() > 1e-4
    np.testing.assert_array_equal(
        np.asarray(_mixtrim_leaf_call(x, m, f, mode=mode, block_rows=None,
                                      interpret=True)),
        np.asarray(_mixtrim_leaf_call(x, m, f, mode=mode, block_rows=None,
                                      interpret=True, one_pass=False)))
