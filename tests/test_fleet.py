"""Fleet engine tests.

The load-bearing ones:

* a lane inside a B=8 bucket is BIT-FOR-BIT the trajectory of the same job
  run alone (batching is a pure throughput lever, never different math);
* one compile per shape bucket, reused across runs and max_lanes chunks;
* the dynamic-f / dynamic-attack kernels agree with the static single-
  scenario paths they generalize.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AggregatorSpec
from repro.core import robust as robust_lib
from repro.core.attacks import apply_attack_dyn, apply_attack_tree, dyn_attack_id
from repro.fed import (
    ClientConfig, FedConfig, FedServer, RotatingByzantine, constant_attack,
    ramp_eta, run_rounds, switch_attack,
)
from repro.fleet import (
    FleetJob, FleetRunner, ScenarioSpec, bucket_key, run_fleet,
)
from repro.optim import sgd
from repro.optim.schedules import constant
from repro.serving import FleetService


def _quad_loss(centers):
    def loss_fn(params, batch):
        c = centers[batch["idx"][0]]
        return 0.5 * jnp.sum((params["theta"] - c) ** 2), {}
    return loss_fn


def _centers(seed, n, d):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, d)), jnp.float32)


def _idx_batch_fn(cohort, n_flip, rng):
    return {"idx": np.asarray(cohort)[:, None, None]}


_N, _M, _D = 10, 6, 5
_CENTERS = _centers(0, _N, _D)
_LOSS = _quad_loss(_CENTERS)
_OPT = sgd(clip=1.0)


def _job(label, *, f=2, schedule=None, seed=0, rounds=5, rule="cwtm",
         pre="nnm", algorithm="dshb", beta=0.9, local_steps=0,
         n=_N, m=_M, lr=0.1, backend="auto"):
    cfg = FedConfig(n_clients=n, clients_per_round=m, f=f,
                    agg=AggregatorSpec(rule=rule, f=f, pre=pre,
                                       backend=backend),
                    client=ClientConfig(local_steps=local_steps,
                                        local_lr=0.05, algorithm=algorithm,
                                        beta=beta))
    return FleetJob(label=label, cfg=cfg, loss_fn=_LOSS, optimizer=_OPT,
                    params={"theta": jnp.zeros((_D,), jnp.float32)},
                    batch_fn=_idx_batch_fn, rounds=rounds, seed=seed,
                    schedule=schedule or constant_attack("none"),
                    lr_fn=lambda r: lr)


# ---------------------------------------------------------------------------
# Acceptance: B=8 fleet lane == the same job run alone, bit for bit.
# ---------------------------------------------------------------------------

def test_b8_fleet_bitwise_equals_eight_single_runs():
    jobs = [
        _job("alie", f=2, schedule=constant_attack("alie", 3.0), seed=0),
        _job("sf", f=3, schedule=constant_attack("sf"), seed=1),
        _job("clean", f=0, schedule=constant_attack("none"), seed=2),
        _job("foe_ramp", f=2, schedule=ramp_eta("foe", 1.0, 6.0, 4), seed=3),
        _job("switch", f=2,
             schedule=switch_attack((0, "none"), (2, "mimic")), seed=4),
        _job("short", f=2, schedule=constant_attack("alie", 8.0), seed=5,
             rounds=3),                      # exercises the active freeze
        _job("lf", f=3, schedule=constant_attack("lf"), seed=6),
        _job("beta5", f=2, schedule=constant_attack("alie", 2.0), seed=7,
             beta=0.5, lr=0.2),
    ]
    runner = FleetRunner(jobs)
    fleet = runner.run()
    assert runner.n_buckets == 1 and runner.trace_count == 1

    for job, res in zip(jobs, fleet):
        solo = FleetRunner([job]).run()[0]
        assert solo.history.rounds == res.history.rounds == job.rounds
        assert solo.history.loss == res.history.loss
        assert solo.history.kappa_hat == res.history.kappa_hat
        assert solo.history.direction_norm == res.history.direction_norm
        for a, b in zip(jax.tree_util.tree_leaves(solo.state),
                        jax.tree_util.tree_leaves(res.state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for ca, cb in zip(solo.history.cohorts, res.history.cohorts):
            np.testing.assert_array_equal(ca, cb)


def test_b8_pallas_backend_one_compile_matches_solo():
    """Acceptance: a B=8 bucket on the pallas backend (interpret mode off-
    TPU) still compiles once per shape bucket, per-lane results equal the
    solo pallas run, and the kernel dispatch is visible + fallback-free
    (cohort m=8 is a power of two, so the fused mixtrim kernel runs)."""
    from repro.kernels import dispatch as kdispatch
    scheds = [constant_attack("alie", 3.0), constant_attack("sf"),
              constant_attack("none"), ramp_eta("foe", 1.0, 6.0, 4)]
    jobs = [_job(f"p{i}", f=(i % 3) + 1, seed=i, n=12, m=8,
                 schedule=scheds[i % len(scheds)], backend="pallas")
            for i in range(8)]
    runner = FleetRunner(jobs)
    fleet = runner.run()
    assert runner.n_buckets == 1 and runner.trace_count == 1
    rec = kdispatch.last_dispatch()
    assert rec is not None and rec.backend == "pallas" and rec.dyn
    assert rec.fallbacks == [], rec.describe()

    for job, res in zip(jobs, fleet):
        solo = FleetRunner([job]).run()[0]
        np.testing.assert_allclose(res.history.loss, solo.history.loss,
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(res.history.direction_norm,
                                   solo.history.direction_norm,
                                   rtol=1e-6, atol=1e-7)
        for a, b in zip(jax.tree_util.tree_leaves(solo.state),
                        jax.tree_util.tree_leaves(res.state)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)


def test_pallas_backend_is_own_shape_bucket():
    """backend is compiled-round key material: mixing backends must split
    the bucket (different kernels inside the round), not silently share."""
    jobs = [_job("x", seed=0, n=12, m=8, backend="xla"),
            _job("p", seed=0, n=12, m=8, backend="pallas")]
    assert bucket_key(jobs[0]) != bucket_key(jobs[1])
    runner = FleetRunner(jobs)
    res = runner.run()
    assert runner.n_buckets == 2 and runner.trace_count == 2
    # same math, different kernels: trajectories agree to float tolerance
    np.testing.assert_allclose(res[0].history.loss, res[1].history.loss,
                               rtol=1e-5, atol=1e-6)


def test_fleet_matches_single_scenario_engine():
    """Same seeds, same host rng conventions: the fleet must track the
    static `run_rounds` engine to float tolerance (the compiled math is
    masked/dynamic rather than sliced/static, so bitwise is not expected)."""
    f, rounds = 2, 5
    agg = AggregatorSpec(rule="cwtm", f=f, pre="nnm")
    cfg = FedConfig(n_clients=_N, clients_per_round=_M, f=f, agg=agg,
                    client=ClientConfig(algorithm="dshb", beta=0.9))
    server = FedServer(_LOSS, _OPT, cfg, constant(0.1))
    state = server.init_state({"theta": jnp.zeros((_D,), jnp.float32)})
    _, ref_hist = run_rounds(server, state, _idx_batch_fn, rounds,
                             schedule=constant_attack("alie", 3.0), seed=42)

    res = run_fleet([_job("x", f=f, rounds=rounds, seed=42,
                          schedule=constant_attack("alie", 3.0))])[0]
    np.testing.assert_allclose(res.history.loss, ref_hist.loss,
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(res.history.direction_norm,
                               ref_hist.direction_norm, rtol=1e-4, atol=1e-6)
    for ca, cb in zip(res.history.cohorts, ref_hist.cohorts):
        np.testing.assert_array_equal(ca, cb)   # identical host sampling


# ---------------------------------------------------------------------------
# Shape buckets + compile cache.
# ---------------------------------------------------------------------------

def test_one_compile_per_shape_bucket():
    jobs = [_job("a", seed=0), _job("b", seed=1),
            _job("small", seed=2, n=8, m=4),      # different cohort shape
            _job("c", seed=3)]
    runner = FleetRunner(jobs)
    runner.run()
    assert runner.n_buckets == 2
    assert runner.trace_count == 2
    runner.run()                                   # reuse, no retrace
    assert runner.trace_count == 2


def test_max_lanes_chunks_share_compile_and_results():
    jobs = [_job(f"j{i}", seed=i, schedule=constant_attack("alie", 2.0))
            for i in range(4)]
    batched = FleetRunner(jobs)
    seq = FleetRunner(jobs, max_lanes=1)
    res_b, res_s = batched.run(), seq.run()
    assert batched.trace_count == 1
    assert seq.trace_count == 1                   # chunks share the cache
    for b, s in zip(res_b, res_s):
        assert b.history.loss == s.history.loss


def test_bucket_key_separates_static_skeleton_only():
    base = _job("a", seed=0)
    assert bucket_key(_job("b", seed=9, f=3, rounds=99,
                           schedule=constant_attack("sf"), beta=0.1,
                           lr=0.7)) == bucket_key(base)
    assert bucket_key(_job("c", rule="gm")) != bucket_key(base)
    assert bucket_key(_job("d", local_steps=2)) != bucket_key(base)
    assert bucket_key(_job("e", m=4)) != bucket_key(base)


# ---------------------------------------------------------------------------
# Job validation.
# ---------------------------------------------------------------------------

def test_fleet_rejects_mda_and_optimized_attacks():
    with pytest.raises(ValueError, match="mda"):
        _job("bad", rule="mda")
    with pytest.raises(ValueError, match="alie_opt"):
        _job("bad", schedule=constant_attack("alie_opt"))
    with pytest.raises(ValueError, match="bucket_size"):
        _job("bad", pre="bucketing")


def test_rotating_identity_and_local_steps_in_fleet():
    jobs = [
        _job("rot", f=3, schedule=constant_attack("alie", 4.0), seed=0,
             local_steps=2),
        _job("fix", f=2, schedule=constant_attack("foe", 3.0), seed=1,
             local_steps=2),
    ]
    jobs[0].byz_identity = RotatingByzantine(_N, 3, period=2)
    runner = FleetRunner(jobs)
    res = runner.run()
    assert runner.trace_count == 1
    for job, r in zip(jobs, res):
        solo = FleetRunner([job]).run()[0]
        assert solo.history.loss == r.history.loss


# ---------------------------------------------------------------------------
# Dynamic kernels vs the static single-scenario paths.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stack_tree():
    rng = np.random.default_rng(7)
    return {"a": jnp.asarray(rng.normal(size=(9, 5)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(9, 3, 2)), jnp.float32)}


@pytest.mark.parametrize("rule", ["cwtm", "cwmed", "meamed", "average",
                                  "krum", "multikrum", "gm"])
@pytest.mark.parametrize("pre", [None, "nnm", "bucketing"])
def test_dyn_aggregation_matches_static(stack_tree, rule, pre):
    key = jax.random.PRNGKey(3)
    for f in (0, 2, 3):
        spec = AggregatorSpec(rule=rule, f=f, pre=pre, bucket_size=2)
        stat = robust_lib.robust_aggregate(stack_tree, spec, key=key)
        dyn = robust_lib.robust_aggregate(stack_tree, spec, f=jnp.int32(f),
                                          key=key)
        for d, s in zip(jax.tree_util.tree_leaves(dyn),
                        jax.tree_util.tree_leaves(stat)):
            np.testing.assert_allclose(np.asarray(d), np.asarray(s),
                                       rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("fam,eta", [("none", 0.0), ("alie", 1.7),
                                     ("foe", 3.0), ("sf", 0.0),
                                     ("mimic", 0.0)])
def test_dyn_attack_matches_static(stack_tree, fam, eta):
    for f in (0, 2, 3):
        dyn = apply_attack_dyn(jnp.int32(dyn_attack_id(fam)), stack_tree,
                               jnp.int32(f), eta=jnp.float32(eta))
        stat = apply_attack_tree(fam, stack_tree, f,
                                 eta=eta if fam in ("alie", "foe") else None)
        for d, s in zip(jax.tree_util.tree_leaves(dyn),
                        jax.tree_util.tree_leaves(stat)):
            np.testing.assert_allclose(np.asarray(d), np.asarray(s),
                                       rtol=2e-5, atol=2e-6)


def test_batched_aggregate_is_vmapped_dyn(stack_tree):
    fs = jnp.asarray([0, 2, 3], jnp.int32)
    bt = jax.tree_util.tree_map(
        lambda leaf: jnp.stack([leaf, 2 * leaf, leaf + 1]), stack_tree)
    spec = AggregatorSpec(rule="cwtm", f=0, pre="nnm")
    out = jax.vmap(lambda t, f: robust_lib.robust_aggregate(t, spec, f=f))(
        bt, fs)
    for lane, f in enumerate((0, 2, 3)):
        single = robust_lib.robust_aggregate(
            jax.tree_util.tree_map(lambda leaf, k=lane: leaf[k], bt),
            spec, f=jnp.int32(f))
        for a, b in zip(jax.tree_util.tree_leaves(single),
                        jax.tree_util.tree_leaves(
                            jax.tree_util.tree_map(
                                lambda leaf, k=lane: leaf[k], out))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Registry specs + serving front door.
# ---------------------------------------------------------------------------

def test_scenario_specs_share_buckets():
    specs = [ScenarioSpec("iid_baseline", seed=s, rounds=3)
             for s in range(2)]
    runner = FleetRunner(specs)
    res = runner.run()
    assert runner.n_buckets == 1 and runner.trace_count == 1
    for r in res:
        assert r.history.rounds == 3
        assert np.isfinite(r.history.loss).all()


def test_fleet_service_submit_poll_drain():
    svc = FleetService()
    a = svc.submit(ScenarioSpec("iid_baseline", seed=0, rounds=2))
    b = svc.submit(ScenarioSpec("iid_baseline", seed=1, rounds=3))
    assert svc.poll(a)["status"] == "queued" and svc.pending == 2
    assert svc.drain() == [a, b] and svc.pending == 0
    pa, pb = svc.poll(a), svc.poll(b)
    assert pa["status"] == pb["status"] == "done"
    assert pa["result"].history.rounds == 2
    assert pb["result"].history.rounds == 3
    assert svc.last_trace_count == 1            # one shared shape bucket
    with pytest.raises(KeyError):
        svc.poll(999)
    with pytest.raises(TypeError):
        svc.submit("not a job")


def test_fleet_service_reuses_compiles_across_drains():
    """A tenant resubmitting the same scenario shape (and lane count) in a
    later drain must not pay the XLA compile again — the service's
    amortization contract.  A different lane count is a different vmapped
    shape and legitimately traces once more.  ``chunk=1`` pins the segment
    length, so trace counts depend only on (bucket shape, lane count) —
    the continuous engine otherwise sizes segments to each wave's
    horizon."""
    svc = FleetService(chunk=1)
    svc.submit(_job("first", seed=0, rounds=2))
    svc.drain()
    assert svc.last_trace_count == 1
    b = svc.submit(_job("second", seed=1, rounds=3))
    svc.drain()
    assert svc.last_trace_count == 0            # same shape + B: cache hit
    assert svc.poll(b)["result"].history.rounds == 3
    svc.submit(_job("pair0", seed=2, rounds=2))
    svc.submit(_job("pair1", seed=3, rounds=2))
    svc.drain()
    assert svc.last_trace_count == 1            # new B=2 shape: one trace
