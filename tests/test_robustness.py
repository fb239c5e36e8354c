"""Adversarial-robustness subsystem tests (src/repro/robustness/ + the
AutoGM rule + nan/inf attacks + data poisoning + breakdown sweeps).

Covers:
* AutoGM — numpy oracle parity, outlier downweighting, static == dyn-f,
  vmapped fleet batching, one-compile-per-shape, explicit (never silent)
  xla dispatch record under Pallas backends;
* core.theory — ``breakdown_point`` / ``max_tolerable_f`` /
  ``composed_kappa`` values for the rule zoo;
* nan/inf attack family on the static / scan / dyn paths, and the
  finite-masked moment estimators that keep ALIE-style attacks finite
  when an honest row is already faulty;
* the quarantine guard — detection, replacement, bitwise no-op, taps;
* data poisoning — labelflip rate=1.0 ==bit the "lf" attack, rate=0 a
  no-op, fleet rate sweeps in ONE bucket / ONE compile;
* run_breakdown on a tiny grid.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import robust as robust_lib
from repro.core import theory
from repro.core.aggregators import aggregate, autogm
from repro.core.attacks import (
    DYN_ATTACK_FAMILIES, apply_attack_dyn, apply_attack_scan,
    apply_attack_tree, dyn_attack_id,
)
from repro.core.types import AggregatorSpec
from repro.fed import (
    ClientConfig, FedConfig, FedServer, PoisonConfig, constant_attack,
    poison_batch, run_rounds,
)
from repro.fed.scenarios import build_scenario, get_scenario
from repro.fleet.runner import FleetRunner, ScenarioSpec, job_from_spec
from repro.kernels import dispatch as kdispatch
from repro.optim import sgd
from repro.optim.schedules import constant
from repro.robustness import (
    QuarantineConfig, frontier_table, quarantine_stack, run_breakdown,
)


# ---------------------------------------------------------------------------
# AutoGM: adaptively-weighted geometric median.
# ---------------------------------------------------------------------------

def _np_project_simplex(v):
    u = np.sort(v)[::-1]
    css = np.cumsum(u)
    idx = np.arange(1, len(v) + 1, dtype=np.float32)
    cond = u + (1.0 - css) / idx > 0.0
    rho = max(int(cond.sum()) - 1, 0)
    theta = (1.0 - css[rho]) / np.float32(rho + 1)
    return np.maximum(v + theta, 0.0)


def _np_autogm(x, lamb=1.0, outer_iters=4, gm_iters=8, eps=1e-8):
    """Vector-space replica of the gram-space solver in repro.core.gram."""
    x = np.asarray(x, np.float32)
    n = x.shape[0]

    def dists(c):
        z = c @ x
        sq = (x * x).sum(1) - 2.0 * (x @ z) + z @ z
        return np.sqrt(np.maximum(sq, 0.0) + eps)

    def weiszfeld(w, c):
        for _ in range(gm_iters):
            inv = w / dists(c)
            c = inv / max(inv.sum(), eps)
        return c

    uniform = np.full((n,), 1.0 / n, np.float32)
    c = weiszfeld(uniform, uniform)
    lamb_eff = max(lamb * dists(c).mean(), eps)
    for _ in range(outer_iters):
        w = _np_project_simplex(-dists(c) / (2.0 * lamb_eff))
        c = weiszfeld(w, c)
    return c @ x


def _stack(n=12, d=20, n_out=3, seed=0, scale=30.0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    x[n - n_out:] += scale        # outliers, honest-first convention
    return x


def test_autogm_matches_numpy_oracle():
    x = _stack()
    got = np.asarray(autogm(jnp.asarray(x), 3))
    want = _np_autogm(x)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_autogm_downweights_outliers():
    x = _stack()
    honest_mean = x[:9].mean(0)
    d_auto = np.linalg.norm(np.asarray(autogm(jnp.asarray(x), 3))
                            - honest_mean)
    d_mean = np.linalg.norm(x.mean(0) - honest_mean)
    d_gm = np.linalg.norm(
        np.asarray(aggregate(jnp.asarray(x),
                             AggregatorSpec(rule="gm", f=3, pre=None)))
        - honest_mean)
    # Both robust rules must crush the contaminated mean (which is dragged
    # ~scale * 3/12 toward the outliers).
    assert d_auto < 0.1 * d_mean
    assert d_gm < 0.1 * d_mean


def test_autogm_registered_and_spec_params_flow():
    x = jnp.asarray(_stack())
    spec = AggregatorSpec(rule="autogm", f=3, pre=None, autogm_lamb=1.0,
                          autogm_iters=4)
    via_spec = np.asarray(aggregate(x, spec))
    np.testing.assert_array_equal(via_spec, np.asarray(autogm(x, 3)))
    # lamb changes the weights: huge lamb -> (near) uniform weights, and
    # uniform-weight Weiszfeld is the plain geometric median.
    loose = np.asarray(aggregate(
        x, AggregatorSpec(rule="autogm", f=3, pre=None, autogm_lamb=1e4)))
    assert not np.allclose(via_spec, loose)
    plain_gm = np.asarray(aggregate(
        x, AggregatorSpec(rule="gm", f=3, pre=None)))
    np.testing.assert_allclose(loose, plain_gm, atol=1e-2)


def _tree(n=12, seed=1):
    rng = np.random.default_rng(seed)
    t = {"w": rng.normal(size=(n, 6, 3)).astype(np.float32),
         "b": rng.normal(size=(n, 5)).astype(np.float32)}
    t["w"][n - 2:] += 25.0
    t["b"][n - 2:] -= 25.0
    return jax.tree_util.tree_map(jnp.asarray, t)


def test_autogm_static_equals_dyn_and_batched():
    tree = _tree()
    spec = AggregatorSpec(rule="autogm", f=2, pre="nnm")
    static = robust_lib.robust_aggregate(tree, spec)
    dyn = robust_lib.robust_aggregate(tree, spec, f=jnp.int32(2))
    for a, b in zip(jax.tree_util.tree_leaves(static),
                    jax.tree_util.tree_leaves(dyn)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    # Lane-batched: 3 lanes, different traced f per lane.
    stacked = jax.tree_util.tree_map(lambda l: jnp.stack([l] * 3), tree)
    out = jax.vmap(lambda t, f: robust_lib.robust_aggregate(t, spec, f=f))(
        stacked, jnp.asarray([0, 2, 2], jnp.int32))
    lane2 = jax.tree_util.tree_map(lambda l: l[2], out)
    for a, b in zip(jax.tree_util.tree_leaves(dyn),
                    jax.tree_util.tree_leaves(lane2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_autogm_one_compile_per_shape_in_scan():
    """A scanned round over varying data + traced f traces ONCE (the
    fixed-iteration solver has no data-dependent control flow)."""
    traces = []
    spec = AggregatorSpec(rule="autogm", f=0, pre="nnm")

    @jax.jit
    def round_fn(x, f):
        traces.append(1)
        return robust_lib.robust_aggregate({"p": x}, spec, f=f)["p"]

    rng = np.random.default_rng(0)
    for f in (1, 2, 3):
        round_fn(jnp.asarray(rng.normal(size=(10, 7)), jnp.float32),
                 jnp.int32(f))
    assert len(traces) == 1


def test_autogm_dispatch_records_explicit_xla_fallback():
    """Under a Pallas backend the autogm solve is RECORDED as xla — the
    fallback is explicit, never silent."""
    tree = _tree()
    robust_lib.robust_aggregate(
        tree, AggregatorSpec(rule="autogm", f=2, pre="nnm",
                             backend="pallas"))
    rec = kdispatch.last_dispatch()
    hits = [d for d in rec.decisions if d.primitive == "autogm_coeff"]
    assert hits and hits[0].fell_back, rec.describe()
    assert "autogm" in hits[0].reason

    # On the plain-xla pipeline there is nothing to fall back FROM: the
    # whole pipeline is recorded xla->xla and no fallback appears.
    robust_lib.robust_aggregate(
        tree, AggregatorSpec(rule="autogm", f=2, pre="nnm", backend="xla"))
    rec = kdispatch.last_dispatch()
    assert not any(d.primitive == "autogm_coeff" and d.fell_back
                   for d in rec.decisions)
    assert rec.fallbacks == []


# ---------------------------------------------------------------------------
# core.theory: breakdown points and composed kappa.
# ---------------------------------------------------------------------------

def test_breakdown_point_values():
    for rule in ("krum", "cwtm", "cwmed", "gm", "autogm"):
        assert theory.breakdown_point(rule, 17) == pytest.approx(8 / 17)
        assert theory.breakdown_point(rule, 17, pre="nnm") \
            == pytest.approx(8 / 17)          # NNM preserves the breakdown
        assert theory.max_tolerable_f(rule, 10) == 4
    assert theory.breakdown_point("average", 17) == 0.0
    assert theory.max_tolerable_f("average", 17) == 0


def test_breakdown_point_validation():
    with pytest.raises(ValueError):
        theory.breakdown_point("krum", 17, 9)     # f beyond (n-1)//2
    with pytest.raises(ValueError):
        theory.breakdown_point("nope", 17)
    with pytest.raises(ValueError):
        theory.max_tolerable_f("krum", 0)
    with pytest.raises(ValueError):
        theory.max_tolerable_f("krum", 17, pre="wat")


def test_composed_kappa_autogm():
    n, f = 17, 4
    assert theory.kappa("autogm", n, f) == theory.kappa("gm", n, f)
    assert theory.composed_kappa("autogm", n, f, pre="nnm") \
        == pytest.approx(theory.nnm_kappa(theory.kappa("autogm", n, f),
                                          n, f))
    assert theory.composed_kappa("autogm", n, f) \
        == theory.kappa("autogm", n, f)


# ---------------------------------------------------------------------------
# nan/inf attacks + finite-masked moments.
# ---------------------------------------------------------------------------

def _honest_tree(n=9, seed=3):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.normal(size=(n, 4)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(n, 2, 3)), jnp.float32)}


def test_nan_inf_attacks_static_path():
    tree = _honest_tree()
    for name, pred in (("nan", np.isnan), ("inf", np.isinf)):
        out = apply_attack_tree(name, tree, 3)
        for leaf in jax.tree_util.tree_leaves(out):
            a = np.asarray(leaf)
            assert pred(a[-3:]).all()           # byz rows: all faulty
            assert np.isfinite(a[:-3]).all()    # honest rows untouched


def test_nan_inf_attacks_scan_and_dyn_paths():
    tree = _honest_tree()
    fams = DYN_ATTACK_FAMILIES
    assert "nan" in fams and "inf" in fams
    for name in ("nan", "inf"):
        sid = jnp.int32(fams.index(name))
        out = apply_attack_scan(fams, sid, tree, 2, eta=jnp.float32(0.0))
        a = np.asarray(out["a"])
        assert not np.isfinite(a[-2:]).any() and np.isfinite(a[:-2]).all()
        out = apply_attack_dyn(jnp.int32(dyn_attack_id(name)), tree,
                               jnp.int32(2), eta=jnp.float32(0.0))
        a = np.asarray(out["a"])
        assert not np.isfinite(a[-2:]).any() and np.isfinite(a[:-2]).all()


def test_alie_stays_finite_with_faulty_honest_row():
    """The finite-masked moments: one honest worker already emitting nan
    must not poison the ALIE/FOE statistics into nan for every row."""
    tree = _honest_tree()
    tree = dict(tree)
    tree["a"] = tree["a"].at[0].set(jnp.nan)    # faulty HONEST worker
    for name in ("alie", "foe"):
        out = apply_attack_tree(name, tree, 3, eta=3.0)
        byz = np.asarray(out["a"])[-3:]
        assert np.isfinite(byz).all(), name
    # dyn path too (the masked-moment variant).
    out = apply_attack_dyn(jnp.int32(dyn_attack_id("alie")), tree,
                           jnp.int32(3), eta=jnp.float32(3.0))
    assert np.isfinite(np.asarray(out["a"])[-3:]).all()


def test_finite_moments_bitwise_on_finite_input():
    from repro.core.attacks import _finite_moments
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)
    mean, std = _finite_moments(h)
    np.testing.assert_array_equal(np.asarray(mean), np.asarray(h.mean(0)))
    np.testing.assert_array_equal(np.asarray(std), np.asarray(h.std(0)))


# ---------------------------------------------------------------------------
# Quarantine guard.
# ---------------------------------------------------------------------------

def test_quarantine_detects_nonfinite_and_exploded_rows():
    tree = _honest_tree(n=8)
    tree["a"] = tree["a"].at[1].set(jnp.inf)          # non-finite row
    tree["b"] = tree["b"].at[5].mul(1e4)              # norm-exploded row
    out, info = quarantine_stack(tree, QuarantineConfig(norm_factor=10.0))
    mask = np.asarray(info["mask"])
    assert int(info["count"]) == 2
    np.testing.assert_array_equal(
        mask, np.float32([0, 1, 0, 0, 0, 1, 0, 0]))
    for leaf in jax.tree_util.tree_leaves(out):
        assert np.isfinite(np.asarray(leaf)).all()
    # Replacement is an inlier: within the kept rows' coordinate range.
    a = np.asarray(out["a"])
    kept = np.asarray(tree["a"])[[0, 2, 3, 4, 6, 7]]
    assert (a[1] >= kept.min(0) - 1e-6).all()
    assert (a[1] <= kept.max(0) + 1e-6).all()


def test_quarantine_norm_screen_disabled():
    tree = _honest_tree(n=8)
    tree["b"] = tree["b"].at[5].mul(1e4)
    _, info = quarantine_stack(tree, QuarantineConfig(norm_factor=0.0))
    assert int(info["count"]) == 0


def test_quarantine_noop_is_bitwise():
    tree = _honest_tree(n=8)
    out, info = quarantine_stack(tree, QuarantineConfig())
    assert int(info["count"]) == 0
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_quarantine_config_validation():
    with pytest.raises(ValueError):
        QuarantineConfig(norm_factor=-1.0)


def _quad_fed(guard=None, taps=False, n=10, f=2, d=12):
    rng = np.random.default_rng(0)
    centers = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)

    def loss_fn(params, batch):
        c = centers[batch["idx"][0]]
        return 0.5 * jnp.sum((params["theta"] - c) ** 2), {}

    def batch_fn(cohort, n_flip, rng):
        return {"idx": np.asarray(cohort)[:, None, None]}

    cfg = FedConfig(n_clients=n, clients_per_round=n, f=f,
                    agg=AggregatorSpec(rule="cwtm", f=f, pre="nnm"),
                    client=ClientConfig(algorithm="dshb", beta=0.9),
                    guard=guard, taps=taps)
    server = FedServer(loss_fn, sgd(clip=1.0), cfg, constant(0.1))
    state = server.init_state({"theta": jnp.zeros((d,), jnp.float32)})
    return server, state, batch_fn


@pytest.mark.parametrize("engine", ["loop", "scan"])
def test_guarded_round_survives_nan_workers(engine):
    """f workers emit NaN; the round completes with finite loss and the
    taps pin the quarantine count at m_byz, split onto the byz mask."""
    server, state, batch_fn = _quad_fed(guard=QuarantineConfig(), taps=True)
    state, hist = run_rounds(server, state, batch_fn, 5,
                             schedule=constant_attack("nan"), seed=0,
                             engine=engine)
    assert all(np.isfinite(hist.loss))
    assert np.isfinite(np.asarray(state["params"]["theta"])).all()
    for t in hist.taps:
        assert int(t["quarantined_count"]) == 2
        assert float(np.sum(t["quarantine_mask_byz"])) == 2.0
        assert float(np.sum(t["quarantine_mask_honest"])) == 0.0


@pytest.mark.parametrize("engine", ["loop", "scan"])
def test_guard_noop_run_is_bitwise(engine):
    """Guard enabled but no fault firing: bit-for-bit the unguarded run."""
    sched = constant_attack("alie", 3.0)
    srv_a, st_a, bf = _quad_fed(guard=None)
    st_a, h_a = run_rounds(srv_a, st_a, bf, 6, schedule=sched, seed=0,
                           engine=engine)
    srv_b, st_b, bf = _quad_fed(guard=QuarantineConfig())
    st_b, h_b = run_rounds(srv_b, st_b, bf, 6, schedule=sched, seed=0,
                           engine=engine)
    np.testing.assert_array_equal(np.asarray(st_a["params"]["theta"]),
                                  np.asarray(st_b["params"]["theta"]))
    assert h_a.loss == h_b.loss


def test_untapped_guard_has_no_tap_fields():
    server, state, batch_fn = _quad_fed(guard=QuarantineConfig(), taps=True)
    state, hist = run_rounds(server, state, batch_fn, 2,
                             schedule=constant_attack("none"), seed=0)
    # Guard present, nothing fired: count taps exist and read 0.
    assert all(int(t["quarantined_count"]) == 0 for t in hist.taps)
    server, state, batch_fn = _quad_fed(guard=None, taps=True)
    state, hist = run_rounds(server, state, batch_fn, 2,
                             schedule=constant_attack("none"), seed=0)
    assert all("quarantined_count" not in t for t in hist.taps)


# ---------------------------------------------------------------------------
# Data poisoning.
# ---------------------------------------------------------------------------

def test_poison_config_validation():
    with pytest.raises(ValueError):
        PoisonConfig(kind="wat")
    with pytest.raises(ValueError):
        PoisonConfig(rate=1.5)
    assert PoisonConfig().static_signature() == ("labelflip", "y", "x", 10)


def test_poison_batch_hits_last_rows_at_rate():
    y = jnp.tile(jnp.arange(8)[None, None, :], (4, 1, 1))   # (m=4, L=1, b=8)
    batch = {"y": y, "x": jnp.zeros((4, 1, 8, 3), jnp.float32)}
    cfg = PoisonConfig(kind="labelflip", rate=0.5, n_classes=10)
    out = poison_batch(batch, cfg, 2, rate=jnp.float32(0.5),
                       strength=jnp.float32(0.0),
                       key=jax.random.PRNGKey(0))
    got = np.asarray(out["y"])
    want = np.asarray(y)
    np.testing.assert_array_equal(got[:2], want[:2])         # honest rows
    np.testing.assert_array_equal(got[2:, :, :4], 9 - want[2:, :, :4])
    np.testing.assert_array_equal(got[2:, :, 4:], want[2:, :, 4:])
    np.testing.assert_array_equal(np.asarray(out["x"]),
                                  np.asarray(batch["x"]))


def test_poison_feature_perturbs_only_masked_samples():
    x = jnp.zeros((3, 1, 4, 5), jnp.float32)
    batch = {"y": jnp.zeros((3, 1, 4), jnp.int32), "x": x}
    cfg = PoisonConfig(kind="feature", rate=1.0, strength=2.0)
    out = poison_batch(batch, cfg, 1, rate=jnp.float32(1.0),
                       strength=jnp.float32(2.0),
                       key=jax.random.PRNGKey(1))
    got = np.asarray(out["x"])
    assert np.array_equal(got[:2], np.zeros((2, 1, 4, 5)))
    assert np.abs(got[2]).mean() > 0.5          # gaussian at scale 2


def test_poison_labelflip_rate1_equals_lf_attack():
    """A rate-1.0 label-flip poisoning run is bit-for-bit the scheduled
    "lf" attack (both flip the SAME samples l -> C-1-l, neither consumes
    extra rng)."""
    lf = get_scenario("labelflip_partial")
    lf = dataclasses.replace(lf, rounds=3)
    pz = dataclasses.replace(
        lf, name="lf_as_poison", attack=constant_attack("none"),
        poison=PoisonConfig(kind="labelflip", rate=1.0))
    for engine in ("loop", "scan"):
        outs = []
        for sc in (lf, pz):
            server, state, batch_fn, _ = build_scenario(sc, seed=0)
            state, hist = run_rounds(server, state, batch_fn, 3,
                                     schedule=sc.attack,
                                     byz_identity=sc.byz_identity(),
                                     seed=0, engine=engine)
            outs.append((state, hist))
        (st_a, h_a), (st_b, h_b) = outs
        for a, b in zip(jax.tree_util.tree_leaves(st_a["params"]),
                        jax.tree_util.tree_leaves(st_b["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=engine)
        assert h_a.loss == h_b.loss, engine


def test_poison_rate0_is_bitwise_clean():
    base = get_scenario("poison_labelflip")
    clean = dataclasses.replace(base, name="pz_clean", poison=None,
                                rounds=3)
    zero = dataclasses.replace(
        base, name="pz_zero", rounds=3,
        poison=PoisonConfig(kind="labelflip", rate=0.0))
    outs = []
    for sc in (clean, zero):
        server, state, batch_fn, _ = build_scenario(sc, seed=0)
        state, _ = run_rounds(server, state, batch_fn, 3,
                              schedule=sc.attack,
                              byz_identity=sc.byz_identity(), seed=0)
        outs.append(state)
    for a, b in zip(jax.tree_util.tree_leaves(outs[0]["params"]),
                    jax.tree_util.tree_leaves(outs[1]["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fleet_poison_rate_sweep_is_one_bucket():
    """Rate is a traced per-lane operand: a whole rate grid shares ONE
    bucket and ONE compile; higher rates must not crash or go non-finite."""
    base = get_scenario("poison_labelflip")
    jobs = []
    for rate in (0.0, 0.5, 1.0):
        sc = dataclasses.replace(
            base, name=f"plf_{rate}",
            poison=PoisonConfig(kind="labelflip", rate=rate))
        jobs.append(job_from_spec(ScenarioSpec(scenario=sc, rounds=2)))
    runner = FleetRunner(jobs)
    results = runner.run()
    assert runner.n_buckets == 1 and runner.trace_count == 1
    assert all(np.isfinite(r.history.loss).all() for r in results)


def test_fleet_poison_kind_splits_buckets():
    base = get_scenario("poison_labelflip")
    feat = dataclasses.replace(
        base, name="pf", poison=PoisonConfig(kind="feature", rate=0.5))
    runner = FleetRunner([
        job_from_spec(ScenarioSpec(scenario=base, rounds=1)),
        job_from_spec(ScenarioSpec(scenario=feat, rounds=1))])
    assert runner.n_buckets == 2


def test_new_scenarios_registered_and_run():
    for name in ("poison_labelflip", "poison_feature",
                 "faulty_nan_quarantine"):
        sc = get_scenario(name)
        server, state, batch_fn, _ = build_scenario(sc, seed=0)
        state, hist = run_rounds(server, state, batch_fn, 2,
                                 schedule=sc.attack,
                                 byz_identity=sc.byz_identity(), seed=0)
        assert np.isfinite(hist.loss).all(), name


# ---------------------------------------------------------------------------
# Breakdown sweep (tiny grid).
# ---------------------------------------------------------------------------

def test_run_breakdown_tiny_grid():
    from repro.robustness.breakdown import BreakdownAttack
    report = run_breakdown(
        rules=(("cwtm", "nnm"), ("autogm", "nnm")),
        attacks=(BreakdownAttack("sf", attack="sf"),
                 BreakdownAttack("poison_lf",
                                 poison=PoisonConfig(kind="labelflip",
                                                     rate=1.0))),
        n_clients=6, fs=(1, 2), rounds=3)
    assert set(report["frontier"]) == {
        "nnm-cwtm|sf", "nnm-cwtm|poison_lf",
        "nnm-autogm|sf", "nnm-autogm|poison_lf"}
    for key, front in report["frontier"].items():
        assert 0 <= front <= 2, key
        assert report["cells"][key]["frontier"] == front
    assert report["predicted"]["nnm-cwtm"] == 2
    # 2 rule rows x (vector, poison) signatures = 4 buckets, 1 compile each.
    assert report["n_buckets"] == 4
    assert report["trace_count"] == 4
    table = frontier_table(report)
    assert "nnm-autogm" in table and "poison_lf" in table


def test_breakdown_attack_validation():
    from repro.robustness.breakdown import BreakdownAttack
    with pytest.raises(ValueError):
        BreakdownAttack("bad", attack="sf",
                        poison=PoisonConfig(kind="labelflip"))
