"""Hierarchical aggregation: fused bucketed-gram reduction + 2-D mesh.

The load-bearing acceptance tests (ISSUE 10):

* hier with ``bucket_size=1`` is BITWISE the dense pipeline (the
  permutation is skipped, not merely invertible) on both backends;
* the fused bucketed-gram kernel matches the jnp oracle — including
  ragged tails, ``bucket_size >= n``, bf16 stacks, and means-only mode;
* ``backend="pallas_hier"`` without a multi-device mesh degrades to the
  dense bucketing path RECORDED (requested/used split + pipeline
  decision), surfaced through ``FleetService.last_dispatch`` — never
  silent;
* under a real (forced 8-device) mesh the hier jaxpr holds ZERO
  full-width (n, D) dot/sort equations and matches the dense path;
* the reduced population (ceil(n/s), f) carries the paper's kappa
  accounting: ``composed_kappa(..., hier=True)`` is Lemma 1 evaluated
  at the reduced population and grows monotonically in s.

Mesh tests skip below 2 devices (the CI ``scale`` job forces 8 via
XLA_FLAGS at job level); the degrade tests skip ABOVE 1 device — the
two CI jobs cover complementary halves, like test_shard_dispatch.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AggregatorSpec
from repro.core import robust as robust_lib
from repro.core import theory
from repro.core.bucketing import (
    adjusted_f, bucket_assignment, bucket_counts, bucket_matrix, bucketing,
    clamp_bucket_size, default_bucket_size, num_buckets,
)
from repro.kernels import dispatch as kdispatch
from repro.kernels.bucketgram import (
    bucket_means_gram, bucket_means_gram_ref, pick_block_n,
)

KEY = jax.random.PRNGKey(42)


def _stack(n, d, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, d)), dtype)


# ---------------------------------------------------------------------------
# Bucketing edge cases (satellite: core/bucketing.py).
# ---------------------------------------------------------------------------

def test_ragged_tail_bucket_is_renormalized():
    """n=10, s=4: the tail bucket holds 2 rows and its mean divides by 2,
    not 4 — checked against a manual segment mean over the in-graph
    assignment."""
    n, s, d = 10, 4, 7
    x = _stack(n, d)
    np.testing.assert_array_equal(np.asarray(bucket_counts(n, s)),
                                  [4.0, 4.0, 2.0])
    assign = np.asarray(bucket_assignment(KEY, n, s))
    got, f_adj = bucketing(x, 1, KEY, bucket_size=s)
    assert f_adj == 1
    got = np.asarray(got)
    xs = np.asarray(x)
    for b in range(num_buckets(n, s)):
        np.testing.assert_allclose(got[b], xs[assign == b].mean(axis=0),
                                   rtol=1e-6)


def test_bucket_size_beyond_n_is_global_mean():
    """s >= n collapses to ONE bucket — the global mean — and the
    adjusted budget bottoms out at f' = 0 (no rule can tolerate Byzantine
    inputs in a population of one)."""
    n, d = 6, 5
    x = _stack(n, d)
    got, f_adj = bucketing(x, 2, KEY, bucket_size=100)
    assert got.shape == (1, d) and f_adj == 0
    np.testing.assert_allclose(np.asarray(got)[0],
                               np.asarray(x).mean(axis=0), rtol=1e-6)
    assert clamp_bucket_size(n, 100, 2) == n
    assert adjusted_f(2, 1) == 0


def test_f0_defaults_to_singleton_buckets():
    """f=0 has no variance/robustness trade to make: the default bucket
    size is 1 and bucketing only permutes (same row multiset)."""
    n, d = 8, 3
    assert default_bucket_size(n, 0) == 1
    x = _stack(n, d)
    got, f_adj = bucketing(x, 0, KEY)
    got = np.asarray(got)
    assert got.shape == (n, d) and f_adj == 0
    np.testing.assert_allclose(np.sort(got, axis=0),
                               np.sort(np.asarray(x), axis=0), rtol=1e-6)


def test_bucketing_key_determinism_under_vmap():
    """A vmapped batch of keys reproduces the per-key calls bitwise —
    the permutation is a pure function of the traced key operand."""
    n, s, d = 12, 3, 4
    x = _stack(n, d)
    keys = jax.random.split(KEY, 4)
    batched = jax.vmap(
        lambda k: bucketing(x, 1, k, bucket_size=s)[0])(keys)
    for i, k in enumerate(keys):
        np.testing.assert_array_equal(
            np.asarray(batched[i]),
            np.asarray(bucketing(x, 1, k, bucket_size=s)[0]))


def test_bucketing_preserves_bf16_dtype():
    """Satellite fix: the stage accumulates in fp32 but hands back the
    input dtype, so a bf16 transport stack stays bf16 downstream."""
    x = _stack(16, 8, dtype=jnp.bfloat16)
    out, _ = bucketing(x, 2, KEY, bucket_size=4)
    assert out.dtype == jnp.bfloat16
    ref, _ = bucketing(x.astype(jnp.float32), 2, KEY, bucket_size=4)
    np.testing.assert_array_equal(np.asarray(out, jnp.float32),
                                  np.asarray(ref.astype(jnp.bfloat16),
                                             jnp.float32))


def test_bucket_matrix_matches_bucketing():
    """B @ x IS the bucketing stage (same key): the matrix form the
    fused kernel contracts against agrees with the gather form."""
    n, s, d = 14, 4, 6
    x = _stack(n, d)
    bmat = bucket_matrix(KEY, n, s)
    assert bmat.shape == (num_buckets(n, s), n)
    np.testing.assert_allclose(
        np.asarray(bmat @ x),
        np.asarray(bucketing(x, 1, KEY, bucket_size=s)[0]),
        rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Fused bucketed-gram kernel vs oracle.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,s,d", [(16, 4, 32), (17, 2, 37), (6, 100, 9)])
def test_bucketgram_kernel_matches_oracle(n, s, d):
    x = _stack(n, d, seed=n)
    bmat = bucket_matrix(KEY, n, clamp_bucket_size(n, s, 1))
    y, g = bucket_means_gram(x, bmat, interpret=True)
    y_ref, g_ref = bucket_means_gram_ref(x, bmat)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)


def test_bucketgram_means_only_and_bf16():
    n, s, d = 16, 4, 24
    x = _stack(n, d, dtype=jnp.bfloat16)
    bmat = bucket_matrix(KEY, n, s, dtype=jnp.bfloat16)
    y, g = bucket_means_gram(x, bmat, with_gram=False, interpret=True)
    assert g is None and y.dtype == jnp.bfloat16
    y_ref, _ = bucket_means_gram_ref(x, bmat, with_gram=False)
    np.testing.assert_allclose(np.asarray(y, jnp.float32),
                               np.asarray(y_ref, jnp.float32),
                               rtol=2e-2, atol=2e-2)


def test_pick_block_n_is_lane_aligned():
    assert pick_block_n(100) % 128 == 0 or pick_block_n(100) >= 100
    assert pick_block_n(10240) % 128 == 0


# ---------------------------------------------------------------------------
# Hier pipeline: parity, the s=1 bitwise no-op, dyn, validation.
# ---------------------------------------------------------------------------

def _tree(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(n, d)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)}


@pytest.mark.parametrize("rule", ["cwtm", "krum", "gm", "meamed"])
def test_hier_xla_vs_pallas_parity(rule):
    tree = _tree(32, 40, seed=7)
    kw = dict(rule=rule, f=3, pre="nnm", hier=True, bucket_size=4)
    got_x = robust_lib.robust_aggregate(
        tree, AggregatorSpec(backend="xla", **kw), key=KEY)
    got_p = robust_lib.robust_aggregate(
        tree, AggregatorSpec(backend="pallas", **kw), key=KEY)
    for a, b in zip(jax.tree_util.tree_leaves(got_x),
                    jax.tree_util.tree_leaves(got_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_hier_s1_is_bitwise_dense(backend):
    """bucket_size=1: singleton buckets.  The permutation is SKIPPED (not
    applied-and-inverted), so the result is bit-for-bit the dense
    pipeline — fp reassociation would otherwise leak through every
    downstream sort."""
    tree = _tree(16, 33, seed=3)
    spec_h = AggregatorSpec(rule="cwtm", f=3, pre="nnm", hier=True,
                            bucket_size=1, backend=backend)
    spec_d = AggregatorSpec(rule="cwtm", f=3, pre="nnm", backend=backend)
    got = robust_lib.robust_aggregate(tree, spec_h, key=KEY)
    rec = kdispatch.last_dispatch()
    ref = robust_lib.robust_aggregate(tree, spec_d)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert any(d.primitive == "bucketgram" and d.used == "skipped"
               for d in rec.decisions), rec.describe()


def test_hier_dyn_matches_static():
    tree = _tree(24, 18, seed=9)
    spec = AggregatorSpec(rule="cwtm", f=2, pre="nnm", hier=True,
                          bucket_size=3, backend="xla")
    got_s = robust_lib.robust_aggregate(tree, spec, key=KEY)
    got_d = robust_lib.robust_aggregate(tree, spec, f=jnp.int32(2),
                                        key=KEY)
    for a, b in zip(jax.tree_util.tree_leaves(got_s),
                    jax.tree_util.tree_leaves(got_d)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_hier_validation_errors():
    tree = _tree(16, 8)
    with pytest.raises(ValueError, match="bucket"):
        robust_lib.robust_aggregate(
            tree, AggregatorSpec(rule="cwtm", f=2, pre="bucketing",
                                 hier=True, bucket_size=2), key=KEY)
    with pytest.raises(ValueError, match="sketch"):
        robust_lib.robust_aggregate(
            tree, AggregatorSpec(rule="cwtm", f=2, hier=True,
                                 bucket_size=2, sketch_dim=4), key=KEY)
    with pytest.raises(ValueError, match="key"):
        robust_lib.robust_aggregate(
            tree, AggregatorSpec(rule="cwtm", f=2, hier=True,
                                 bucket_size=2))
    with pytest.raises(ValueError, match="bucket_size"):
        robust_lib.robust_aggregate(
            tree, AggregatorSpec(rule="cwtm", f=2, hier=True),
            f=jnp.int32(2), key=KEY)


# ---------------------------------------------------------------------------
# Theory: the reduced population carries the kappa accounting.
# ---------------------------------------------------------------------------

def test_bucketed_population_guards_breakdown():
    assert theory.bucketed_population(64, 4, 4) == (16, 4)
    with pytest.raises(ValueError, match="cannot"):
        theory.bucketed_population(64, 8, 4)      # 16 buckets vs f=8


def test_composed_kappa_hier_is_lemma1_at_reduced_population():
    n, f, s = 256, 8, 4
    n_b = num_buckets(n, s)
    expect = theory.nnm_kappa(theory.kappa("cwtm", n_b, f), n_b, f)
    got = theory.composed_kappa("cwtm", n, f, "nnm", hier=True,
                                bucket_size=s)
    assert got == pytest.approx(expect)


def test_composed_kappa_monotone_in_bucket_size():
    """The s vs kappa trade-off the docs table reports: shrinking the
    population inflates every coefficient."""
    ks = [theory.composed_kappa("cwtm", 10240, 128, "nnm", hier=True,
                                bucket_size=s) for s in (1, 4, 16, 32)]
    assert all(a < b for a, b in zip(ks, ks[1:]))


# ---------------------------------------------------------------------------
# Degrade detectability (single-device hosts) — satellite: the
# dense-bucketing fallback is RECORDED, surfaced via the fleet service.
# ---------------------------------------------------------------------------

def _hier_job():
    from repro.fed import ClientConfig, FedConfig, constant_attack
    from repro.fleet import FleetJob
    from repro.optim import sgd

    def loss_fn(params, batch):
        return 0.5 * jnp.sum(params["theta"] ** 2), {}

    cfg = FedConfig(n_clients=10, clients_per_round=6, f=2,
                    agg=AggregatorSpec(rule="cwtm", f=2, pre="nnm",
                                       hier=True, bucket_size=2,
                                       backend="pallas_hier"),
                    client=ClientConfig(local_steps=0, local_lr=0.05,
                                        algorithm="dshb", beta=0.9))
    return FleetJob(label="hier", cfg=cfg, loss_fn=loss_fn,
                    optimizer=sgd(clip=1.0),
                    params={"theta": jnp.zeros((5,), jnp.float32)},
                    batch_fn=lambda cohort, n_flip, rng:
                        {"idx": np.asarray(cohort)[:, None, None]},
                    rounds=2, schedule=constant_attack("none"))


def test_pallas_hier_degrades_to_dense_bucketing_recorded():
    """Forcing pallas_hier without a mesh runs the dense bucketing path
    and the record says so: requested/used split, hier flag, bucket
    size, and a pipeline-level fallback decision."""
    if jax.device_count() > 1:
        pytest.skip("degrade only happens on single-device hosts")
    tree = _tree(16, 20, seed=5)
    spec = AggregatorSpec(rule="cwtm", f=3, pre="nnm", hier=True,
                          bucket_size=4, backend="pallas_hier")
    got = robust_lib.robust_aggregate(tree, spec, key=KEY)
    rec = kdispatch.last_dispatch()
    assert rec.requested == "pallas_hier" and rec.backend == "xla"
    assert rec.hier and rec.bucket_size == 4
    assert any(d.primitive == "pipeline" and d.fell_back
               for d in rec.decisions), rec.describe()
    ref = robust_lib.robust_aggregate(
        tree, AggregatorSpec(rule="cwtm", f=3, pre="nnm", hier=True,
                             bucket_size=4, backend="xla"), key=KEY)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fleet_service_surfaces_hier_degrade():
    from repro.serving import FleetService
    if jax.device_count() > 1:
        pytest.skip("degrade only happens on single-device hosts")
    svc = FleetService()
    svc.submit(_hier_job())
    with pytest.deprecated_call():
        svc.drain()
    rec = svc.last_dispatch
    assert rec is not None, "drain must snapshot a fresh trace's record"
    assert rec.requested == "pallas_hier" and rec.backend == "xla"
    assert rec.hier
    assert any(d.primitive == "pipeline" and d.fell_back
               for d in rec.decisions), rec.describe()


def test_fleet_hier_lane_requires_bucket_size():
    from repro.fed import FedConfig
    import dataclasses as dc
    job = _hier_job()
    bad_agg = dc.replace(job.cfg.agg, bucket_size=None)
    with pytest.raises(ValueError, match="bucket_size"):
        dc.replace(job, cfg=dc.replace(job.cfg, agg=bad_agg))
    assert isinstance(job.cfg, FedConfig)


def test_bucket_key_separates_hier_lanes():
    from repro.fleet import bucket_key
    import dataclasses as dc
    job = _hier_job()
    plain_agg = dc.replace(job.cfg.agg, hier=False, backend="xla")
    plain = dc.replace(job, cfg=dc.replace(job.cfg, agg=plain_agg))
    assert bucket_key(job) != bucket_key(plain)


# ---------------------------------------------------------------------------
# Mesh structure (forced multi-device hosts — the CI `scale` job).
# ---------------------------------------------------------------------------

def _needs_mesh():
    if jax.device_count() < 2:
        pytest.skip("needs a multi-device host (forced 8-device CI job)")


def test_hier_mesh_resolves_worker_axis():
    _needs_mesh()
    from repro.launch.mesh import hier_aggregation_mesh
    ctx = hier_aggregation_mesh()
    assert ctx is not None
    mesh, worker_axis, model_axis = ctx
    if jax.device_count() >= 4:
        assert worker_axis is not None
        assert mesh.shape[worker_axis] * mesh.shape[model_axis] == \
            jax.device_count()


def test_pallas_hier_mesh_parity_and_record():
    _needs_mesh()
    tree = _tree(64, 48, seed=11)
    spec_m = AggregatorSpec(rule="cwtm", f=4, pre="nnm", hier=True,
                            bucket_size=4, backend="pallas_hier")
    got = robust_lib.robust_aggregate(tree, spec_m, key=KEY)
    rec = kdispatch.last_dispatch()
    assert rec.backend == "pallas_hier"
    assert rec.mesh_devices == jax.device_count()
    assert not rec.fallbacks, rec.describe()
    ref = robust_lib.robust_aggregate(
        tree, AggregatorSpec(rule="cwtm", f=4, pre="nnm", hier=True,
                             bucket_size=4, backend="xla"), key=KEY)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_pallas_hier_mesh_has_zero_wide_ops():
    """Acceptance: under the mesh the (n, D) stack is reduced in place —
    no full-width dot/sort equation anywhere in the jaxpr."""
    _needs_mesh()
    n, d = 64, 48
    tree = _tree(n, d, seed=11)
    spec = AggregatorSpec(rule="cwtm", f=4, pre="nnm", hier=True,
                          bucket_size=4, backend="pallas_hier")
    wide = kdispatch.count_wide_ops(
        lambda t: robust_lib.robust_aggregate(t, spec, key=KEY), tree,
        n=n, width=d)
    assert wide == 0
