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
