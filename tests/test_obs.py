"""Unified telemetry layer (repro.obs): taps + runtime tracing.

VERIFIES
* HEALTH TAPS are pure side-outputs: a tapped run is bit-for-bit equal to
  the untapped run on model state and loss — trainer, fed, and fleet —
  and adds NO extra traces or host transfers (engine counters);
* tap VALUES match a hand-rolled NumPy oracle on a small round, on both
  the static-f and the traced-f (fleet) paths;
* the RUNTIME registry: events/spans/counters, bounded ring, JSONL
  round-trip (export -> parse -> same events), Chrome trace as valid JSON
  with nondecreasing ``ts``;
* the DISPATCH RING: ``dispatch_history(limit=)``, ``last_dispatch()`` as
  the head, the monotone ``dispatch_count()``, and the ``obs.runtime``
  re-export being the same objects;
* FedHistory alignment: NaN kappa placeholders + nanmean summary + taps
  columns; and one fleet-service drain exported END TO END (compiles,
  segments, dispatch decisions all visible with timestamps);
* STAGE TAGS: the robust D-SHB step at tiny SmolLM sizes carries every
  stage as a ``robust_stage`` frontend attribute (the Pallas custom calls
  ``aggregate``), and the tags change no instruction of the compiled
  program;
* the PROFILER CLOCK: a runtime span lands on the profile's host plane;
  the compile counters fed by ``jax.monitoring``; one ``data.batch`` span
  per batch of the input pipeline.
"""
import contextlib
import functools
import glob
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import AggregatorSpec
from repro.core.robust import robust_aggregate
from repro.data.pipeline import WorkerDataset, worker_batches
from repro.fed import (
    ClientConfig, FedConfig, FedServer, constant_attack, run_rounds,
)
from repro.fed.metrics import FedHistory
from repro.fed.schedules import AttackPhase, AttackSchedule
from repro.fleet import FleetJob, FleetRunner
from repro.kernels import dispatch as kdispatch
from repro.obs import runtime as obs_runtime
from repro.obs import stages
from repro.optim import sgd
from repro.optim.schedules import constant
from repro.serving.engine import FleetService
from repro.training import ByzantineConfig, TrainerConfig, train_loop

_N, _M, _D = 10, 8, 6


def _centers(n=_N, d=_D, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, d)), jnp.float32)


_CENTERS = _centers()


def _quad_loss(params, batch):
    c = _CENTERS[batch["idx"][0]]
    return 0.5 * jnp.sum((params["theta"] - c) ** 2), {}


def _idx_batch_fn(cohort, n_flip, rng):
    return {"idx": np.asarray(cohort)[:, None, None]}


def _params():
    return {"theta": jnp.zeros((_D,), jnp.float32)}


# ---------------------------------------------------------------------------
# Taps vs a hand-rolled NumPy oracle.
# ---------------------------------------------------------------------------

def _numpy_taps(x, r, n_honest, f, rule, pre):
    """Reference implementation, plain numpy, no shared code with taps.py."""
    x = np.asarray(x, np.float64)
    r = np.asarray(r, np.float64)
    n = x.shape[0]
    hm = x[:n_honest].mean(axis=0)
    out = {
        "dist_honest": np.linalg.norm(r - hm),
        "cos_honest": float(r @ hm) / (np.linalg.norm(r)
                                       * np.linalg.norm(hm) + 1e-20),
    }
    m = None
    if pre == "nnm":
        d2 = ((x[:, None, :] - x[None, :, :]) ** 2).sum(-1)
        k = n - f
        m = np.zeros((n, n))
        for i in range(n):
            nearest = np.argsort(d2[i], kind="stable")[:k]
            m[i, nearest] = 1.0 / k
        out["neighbor_count"] = (m > 0).sum(axis=0).astype(float)
        col = m.sum(axis=0) / n
        out["mix_mass"] = col
        out["byz_mix_mass"] = col[n_honest:].sum()
        out["honest_mix_mass"] = col[:n_honest].sum()
    if rule == "cwtm" and pre in (None, "nnm"):
        y = x if m is None else m @ x
        ys = np.sort(y, axis=0)
        trimmed = (y < ys[f][None, :]) | (y > ys[n - 1 - f][None, :])
        out["trim_frac"] = trimmed.mean(axis=1)
    return out


@pytest.mark.parametrize("rule,pre", [("cwtm", "nnm"), ("cwtm", None),
                                      ("gm", "nnm"), ("cwmed", None)])
def test_health_taps_match_numpy_oracle(rule, pre):
    n, f, d = 9, 2, 7
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    stack = {"w": x[:, :4], "b": x[:, 4:]}
    spec = AggregatorSpec(rule=rule, f=f, pre=pre)
    agg = robust_aggregate(stack, spec, key=jax.random.PRNGKey(0))
    taps = obs.health_taps(stack, agg, n_honest=n - f, f=f,
                           rule=rule, pre=pre)
    r_flat = np.concatenate([np.asarray(agg["w"]).reshape(-1),
                             np.asarray(agg["b"]).reshape(-1)])
    want = _numpy_taps(np.asarray(x), r_flat, n - f, f, rule, pre)
    got = {k: np.asarray(v) for k, v in taps.to_dict().items()}
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-5, atol=2e-5,
                                   err_msg=k)


def test_health_taps_dyn_matches_static():
    n, f, d = 8, 2, 5
    rng = np.random.default_rng(1)
    stack = {"x": jnp.asarray(rng.normal(size=(n, d)), jnp.float32)}
    spec = AggregatorSpec(rule="cwtm", f=f, pre="nnm")
    agg = robust_aggregate(stack, spec, key=jax.random.PRNGKey(0))
    static = obs.health_taps(stack, agg, n_honest=n - f, f=f,
                             rule="cwtm", pre="nnm")
    dyn = obs.health_taps(stack, agg, n_honest=jnp.int32(n - f),
                          f=jnp.int32(f), rule="cwtm", pre="nnm")
    for k, v in static.to_dict().items():
        np.testing.assert_allclose(np.asarray(v),
                                   np.asarray(dyn.to_dict()[k]),
                                   rtol=1e-6, err_msg=k)


def test_health_taps_structure_gates():
    """NNM taps need pre='nnm'; trim taps need cwtm without bucketing."""
    stack = {"x": jnp.ones((6, 3), jnp.float32)}
    agg = {"x": jnp.ones((3,), jnp.float32)}
    t = obs.health_taps(stack, agg, n_honest=5, f=1, rule="gm", pre=None)
    assert t.neighbor_count is None and t.trim_frac is None
    assert set(t.to_dict()) == {"dist_honest", "cos_honest"}
    t = obs.health_taps(stack, agg, n_honest=5, f=1, rule="cwtm",
                        pre="bucketing")
    assert t.trim_frac is None      # bucketed trim acts on bucket means


# ---------------------------------------------------------------------------
# Parity: tapped == untapped bit-for-bit; no extra traces or transfers.
# ---------------------------------------------------------------------------

def _trainer_run(taps, engine, steps=8):
    cfg = TrainerConfig(algorithm="dshb",
                        agg=AggregatorSpec(rule="cwtm", f=3, pre="nnm"),
                        byz=ByzantineConfig(f=3, attack="alie", eta=3.0),
                        taps=taps)
    return train_loop(_quad_loss, _params(), {"idx": np.arange(_N)[:, None]},
                      sgd(clip=1.0), cfg, constant(0.1), steps,
                      engine=engine)


def test_trainer_taps_parity_and_columns():
    p_on, out_on = _trainer_run(True, "scan")
    p_off, out_off = _trainer_run(False, "scan")
    np.testing.assert_array_equal(np.asarray(p_on["theta"]),
                                  np.asarray(p_off["theta"]))
    assert out_on["history"]["loss"] == out_off["history"]["loss"]
    assert out_on["history"]["kappa_hat"] == out_off["history"]["kappa_hat"]
    cols = out_on["history"]["taps"]
    assert cols["dist_honest"].shape == (8,)
    assert cols["neighbor_count"].shape == (8, _N)
    assert cols["trim_frac"].shape == (8, _N)
    assert "taps" not in out_off["history"]
    # Band semantics: at most 2f values per coordinate fall outside the
    # kept band (exactly 2f when values are distinct — ALIE's identical
    # Byzantine rows + NNM row-collapse produce ties, so <= here; the
    # tie-free exact-2f case is covered by the NumPy-oracle test).
    tf = cols["trim_frac"]
    assert (tf >= 0.0).all() and (tf <= 1.0).all()
    assert (tf.sum(axis=1) <= 6.0 + 1e-5).all()
    np.testing.assert_allclose(
        cols["byz_mix_mass"] + cols["honest_mix_mass"], 1.0, rtol=1e-6)
    # The scan's taps are bit-for-bit the per-step loop's taps.
    _, out_loop = _trainer_run(True, "loop")
    for k, v in cols.items():
        np.testing.assert_array_equal(v, out_loop["history"]["taps"][k])


def test_trainer_taps_no_extra_traces_or_transfers():
    """The zero-extra-host-traffic contract, asserted on engine counters:
    one trace, one metrics transfer per run — tapped or not."""
    for taps in (False, True):
        _, out = _trainer_run(taps, "scan")
        assert out["scan_report"]["trace_count"] == 1, (taps, out)


def _fed_run(taps, engine, rounds=8):
    cfg = FedConfig(n_clients=_N + 2, clients_per_round=_M, f=2,
                    agg=AggregatorSpec(rule="cwtm", f=2, pre="nnm"),
                    client=ClientConfig(algorithm="dshb", beta=0.9),
                    taps=taps)
    server = FedServer(_quad_loss, sgd(clip=1.0), cfg, constant(0.1))
    state = server.init_state(_params())
    state, hist = run_rounds(server, state, _idx_batch_fn, rounds,
                             schedule=constant_attack("alie", 3.0),
                             seed=0, engine=engine)
    return state, hist, server


def test_fed_taps_parity_and_history():
    s_on, h_on, srv_on = _fed_run(True, "scan")
    s_off, h_off, srv_off = _fed_run(False, "scan")
    np.testing.assert_array_equal(np.asarray(s_on["params"]["theta"]),
                                  np.asarray(s_off["params"]["theta"]))
    assert h_on.loss == h_off.loss
    assert srv_on.last_scan_report["trace_count"] == 1
    assert srv_off.last_scan_report["trace_count"] == 1
    assert all(t is not None for t in h_on.taps)
    assert all(t is None for t in h_off.taps)
    assert h_off.tap_columns() == {}
    cols = h_on.tap_columns()
    assert cols["trim_frac"].shape == (8, _M)
    # Loop engine produces the same taps bit-for-bit.
    _, h_loop, _ = _fed_run(True, "loop")
    for k, v in cols.items():
        np.testing.assert_array_equal(v, h_loop.tap_columns()[k])


def _fleet_job(taps, f, seed, rounds=6):
    cfg = FedConfig(n_clients=_N + 2, clients_per_round=_M, f=f,
                    agg=AggregatorSpec(rule="cwtm", f=f, pre="nnm"),
                    client=ClientConfig(algorithm="dshb", beta=0.9),
                    taps=taps)
    return FleetJob(label=f"f{f}s{seed}", cfg=cfg, loss_fn=_quad_loss,
                    optimizer=_FLEET_OPT, params=_params(),
                    batch_fn=_idx_batch_fn, rounds=rounds, seed=seed,
                    schedule=AttackSchedule((AttackPhase("sf", 0),)))


_FLEET_OPT = sgd(clip=1.0)


def test_fleet_taps_parity_and_demux():
    jobs_on = [_fleet_job(True, 2, 0), _fleet_job(True, 1, 1)]
    jobs_off = [_fleet_job(False, 2, 0), _fleet_job(False, 1, 1)]
    run_on, run_off = FleetRunner(jobs_on), FleetRunner(jobs_off)
    res_on, res_off = run_on.run(), run_off.run()
    # taps is bucket-key material: tapped and untapped never share, yet
    # each fleet still compiles once.
    assert run_on.trace_count == 1 and run_off.trace_count == 1
    for a, b in zip(res_on, res_off):
        np.testing.assert_array_equal(
            np.asarray(a.state["params"]["theta"]),
            np.asarray(b.state["params"]["theta"]))
        assert a.history.loss == b.history.loss
    # Per-lane demux: each job's history carries its own aligned columns,
    # and the traced-f lanes genuinely differ.
    c0 = res_on[0].history.tap_columns()
    c1 = res_on[1].history.tap_columns()
    assert c0["dist_honest"].shape == (6,)
    assert (c0["trim_frac"].sum(axis=1) <= 4.0 + 1e-5).all()
    assert (c1["trim_frac"].sum(axis=1) <= 2.0 + 1e-5).all()
    # The two lanes carry different traced budgets — taps must demux, not
    # broadcast one lane's values.
    assert not np.array_equal(c0["trim_frac"], c1["trim_frac"])


def test_fleet_tapped_and_untapped_jobs_split_buckets():
    runner = FleetRunner([_fleet_job(True, 2, 0), _fleet_job(False, 2, 1)])
    assert runner.n_buckets == 2


# ---------------------------------------------------------------------------
# FedHistory alignment.
# ---------------------------------------------------------------------------

def test_fed_history_kappa_nan_alignment_and_nanmean():
    h = FedHistory()
    cohort = np.arange(4)
    h.record({"loss": 1.0, "lr": 0.1, "direction_norm": 1.0,
              "kappa_hat": 2.0}, cohort=cohort, attack="none", eta=None,
             m_byz=0, f_round=0)
    h.record({"loss": 1.0, "lr": 0.1, "direction_norm": 1.0},
             cohort=cohort, attack="none", eta=None, m_byz=0, f_round=0)
    h.record({"loss": 1.0, "lr": 0.1, "direction_norm": 1.0,
              "kappa_hat": 4.0}, cohort=cohort, attack="none", eta=None,
             m_byz=0, f_round=0)
    # kappa_hat[i] is round i's value — the untracked round holds NaN.
    assert len(h.kappa_hat) == 3
    assert h.kappa_hat[0] == 2.0 and np.isnan(h.kappa_hat[1])
    assert h.kappa_hat[2] == 4.0
    assert h.summary()["mean_kappa_hat"] == pytest.approx(3.0)
    h_none = FedHistory()
    h_none.record({"loss": 1.0, "lr": 0.1, "direction_norm": 1.0},
                  cohort=cohort, attack="none", eta=None, m_byz=0, f_round=0)
    assert h_none.summary()["mean_kappa_hat"] is None


# ---------------------------------------------------------------------------
# Runtime registry + exporters.
# ---------------------------------------------------------------------------

def test_runtime_events_spans_counters_history():
    rt = obs_runtime.Runtime()
    rt.event("a", x=1)
    with rt.span("b", n=2):
        rt.event("a", x=2)
    rt.inc("ticks")
    rt.inc("ticks", 2.0)
    assert [e["name"] for e in rt.history()] == ["a", "a", "b"]
    assert [e["args"]["x"] for e in rt.history(name="a")] == [1, 2]
    assert rt.history(kind="span")[0]["dur"] >= 0.0
    assert rt.history(limit=1)[0]["name"] == "b"
    assert rt.counters() == {"ticks": 3.0}
    rt.reset()
    assert rt.history() == [] and rt.counters() == {}


def test_runtime_ring_is_bounded():
    rt = obs_runtime.Runtime(capacity=8)
    for i in range(20):
        rt.event("e", i=i)
    hist = rt.history()
    assert len(hist) == 8
    assert [e["args"]["i"] for e in hist] == list(range(12, 20))
    assert hist[-1]["seq"] == 20    # lifetime seq survives ring drops


def test_runtime_jsonl_roundtrip(tmp_path):
    rt = obs_runtime.Runtime()
    rt.event("np_arg", val=np.float32(1.5))
    rec = kdispatch.DispatchRecord(requested="auto", backend="xla",
                                   rule="cwtm", pre="nnm")
    rec.decisions.append(kdispatch.KernelDecision("gram", "xla", "xla"))
    rt.event("dataclass_arg", record=rec)
    with rt.span("seg", start=0, end=4):
        pass
    rt.inc("transfers", 3)
    path = tmp_path / "events.jsonl"
    n = rt.export_jsonl(str(path))
    lines = obs_runtime.import_jsonl(str(path))
    assert len(lines) == n == 4
    events = [l for l in lines if l["kind"] != "counter"]
    assert events == rt.snapshot()
    assert events[0]["args"]["val"] == 1.5
    assert events[1]["args"]["record"]["rule"] == "cwtm"
    assert events[1]["args"]["record"]["decisions"][0]["primitive"] == "gram"
    counter = [l for l in lines if l["kind"] == "counter"][0]
    assert counter == {"name": "transfers", "kind": "counter",
                       "ts": counter["ts"], "value": 3.0}


def test_runtime_chrome_trace_valid_and_monotonic(tmp_path):
    rt = obs_runtime.Runtime()
    with rt.span("outer"):
        rt.event("inner")
        with rt.span("nested"):
            pass
    rt.inc("c", 5)
    path = tmp_path / "trace.json"
    n = rt.export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    rows = doc["traceEvents"]
    assert len(rows) == n == 4
    ts = [r["ts"] for r in rows]
    assert ts == sorted(ts)
    phases = {r["name"]: r["ph"] for r in rows}
    assert phases == {"outer": "X", "nested": "X", "inner": "i", "c": "C"}
    for r in rows:
        if r["ph"] == "X":
            assert r["dur"] >= 0.0
        assert {"name", "ph", "pid", "tid", "ts"} <= set(r)


# ---------------------------------------------------------------------------
# Dispatch ring + the obs.runtime re-export.
# ---------------------------------------------------------------------------

def test_dispatch_history_ring_and_count():
    stack = {"x": jnp.asarray(np.random.default_rng(0).normal(size=(8, 5)),
                              jnp.float32)}
    before = kdispatch.dispatch_count()
    robust_aggregate(stack, AggregatorSpec(rule="cwtm", f=2, pre="nnm"),
                     key=jax.random.PRNGKey(0))
    robust_aggregate(stack, AggregatorSpec(rule="gm", f=2),
                     key=jax.random.PRNGKey(0))
    assert kdispatch.dispatch_count() == before + 2
    recent = kdispatch.dispatch_history(limit=2)
    assert [r.rule for r in recent] == ["cwtm", "gm"]
    # last_dispatch is the ring head, identically.
    assert kdispatch.last_dispatch() is recent[-1]
    # The obs.runtime re-export is the same surface, same objects.
    assert obs_runtime.dispatch_history(limit=2)[-1] is recent[-1]
    assert obs_runtime.last_dispatch() is recent[-1]
    assert obs.dispatch_count() == kdispatch.dispatch_count()


def test_dispatch_ring_bounded():
    assert kdispatch.DISPATCH_HISTORY_LIMIT >= 1
    assert len(kdispatch.dispatch_history()) <= \
        kdispatch.DISPATCH_HISTORY_LIMIT


# ---------------------------------------------------------------------------
# End to end: one fleet drain captured in one export.
# ---------------------------------------------------------------------------

def test_fleet_drain_export_end_to_end(tmp_path):
    obs_runtime.reset()
    svc = FleetService(chunk=3)
    svc.submit(_fleet_job(True, 2, 7))
    svc.submit(_fleet_job(True, 1, 8))
    ids = svc.drain()
    assert len(ids) == 2
    # A fresh compile happened, so the drain recorded its dispatch.
    assert svc.last_dispatch is not None and svc.last_dispatch.dyn
    names = [e["name"] for e in obs_runtime.history()]
    assert "fleet.drain" in names          # the drain span
    assert "fleet.trace" in names          # the compile
    assert "fleet.segment" in names        # chunked scan segments
    assert "kernels.dispatch" in names     # the aggregation dispatch
    assert names.count("fleet.segment") == 2    # 6 rounds / chunk=3
    jsonl = tmp_path / "drain.jsonl"
    chrome = tmp_path / "drain.json"
    obs_runtime.export_jsonl(str(jsonl))
    obs_runtime.export_chrome_trace(str(chrome))
    lines = obs_runtime.import_jsonl(str(jsonl))
    events = [l for l in lines if l["kind"] != "counter"]
    assert events == obs_runtime.snapshot()
    # The dispatch decision trail (incl. any fallback reasons) survived
    # serialization with its per-primitive decisions.
    disp = [e for e in events if e["name"] == "kernels.dispatch"]
    assert disp and disp[-1]["args"]["record"]["decisions"]
    doc = json.loads(chrome.read_text())
    ts = [r["ts"] for r in doc["traceEvents"]]
    assert ts == sorted(ts) and len(ts) == len(events) + \
        len([l for l in lines if l["kind"] == "counter"])
    # Cache-hit drain: no new dispatch record -> None, ring untouched.
    svc.submit(_fleet_job(True, 2, 9))
    svc.submit(_fleet_job(True, 1, 10))
    svc.drain()
    assert svc.last_dispatch is None


# ---------------------------------------------------------------------------
# Stage tags on the robust step.
# ---------------------------------------------------------------------------

#: SmolLM's architecture at toy widths.
_TINY_LM = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                d_ff=128, vocab_size=256, head_dim=16)
#: Mellum2's expert layer and layer types at toy widths.
_TINY_MOE = dict(_TINY_LM, num_layers=4, d_ff=32, num_experts=8,
                 experts_per_token=2, experts_held=2, sliding_window=8)
_STEP_STAGES = ("backward", "momentum", "attack", "aggregate", "kappa",
                "optimizer")
#: What a compiled program's text says beside its instructions.
_ANNOTATION = re.compile(
    r",? ?(metadata|frontend_attributes)=\{[^{}]*(\{[^{}]*\}[^{}]*)*\}")


def _tiny_step(backend, arch="smollm-360m", sizes=_TINY_LM):
    """The D-SHB step (n=4, f=1 ALIE, NNM+CWTM) of a tiny SmolLM (or of
    ``arch`` at ``sizes``), jitted with the state donated, and its
    argument shapes."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.training import build_train_step, init_state

    model = build_model(get_config(arch).replace(**sizes))
    n, f = 4, 1
    cfg = TrainerConfig(
        algorithm="dshb", beta=0.9,
        agg=AggregatorSpec(rule="cwtm", pre="nnm", f=f, backend=backend),
        byz=ByzantineConfig(f=f, attack="alie"))
    opt = sgd(clip=2.0)
    step = jax.jit(build_train_step(model.loss, opt, cfg, constant(0.05)),
                   donate_argnums=0)
    state = jax.eval_shape(lambda k: init_state(model.init(k), opt, n, cfg),
                           jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((n, 2, 16), jnp.int32)
    return step, (state, {"tokens": tokens, "labels": tokens},
                  jax.random.PRNGKey(0))


@functools.lru_cache(maxsize=None)
def _compiled_step_text(backend, arch="smollm-360m"):
    sizes = _TINY_MOE if arch == "mellum2-12b-a2.5b" else _TINY_LM
    step, args = _tiny_step(backend, arch, sizes)
    return step.lower(*args).compile().as_text()


def _instructions(text):
    """The compiled program without its annotations: the header and the
    computations, with ``metadata`` and ``frontend_attributes`` removed
    (the source-location tables before the first computation go too)."""
    lines = text.splitlines()
    first = next(i for i, l in enumerate(lines)
                 if l.startswith(("%", "ENTRY")))
    return _ANNOTATION.sub("", "\n".join(lines[:1] + lines[first:]))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_step_carries_every_stage_tag(backend):
    text = _compiled_step_text(backend)
    found = set(re.findall(r'robust_stage="(\w+)"', text))
    assert set(_STEP_STAGES) <= found <= set(stages.STAGES), found


def test_step_kernels_carry_aggregate_tag(monkeypatch):
    """Lowered for a TPU (Mosaic kernels, no chip needed), every Pallas
    custom call of the step carries the ``aggregate`` stage beside its
    own attributes."""
    from repro.kernels import target

    monkeypatch.setattr(target, "on_tpu", lambda: True)
    step, args = _tiny_step("pallas")
    text = step.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    calls = [l for l in text.splitlines() if "@tpu_custom_call" in l]
    assert len(calls) >= 2
    for line in calls:
        assert "mhlo.frontend_attributes" in line
        assert 'robust_stage = "aggregate"' in line


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_stage_tags_change_no_instruction(backend, monkeypatch):
    tagged = _compiled_step_text(backend)
    monkeypatch.setattr(stages, "stage",
                        lambda name: contextlib.nullcontext())
    step, args = _tiny_step(backend)
    plain = step.lower(*args).compile().as_text()
    assert "robust_stage" not in plain
    assert _instructions(tagged) == _instructions(plain)


def test_expert_ops_carry_moe_part_forward_and_backward(monkeypatch):
    """Lowered for a TPU, the tiny Mellum2 step's grouped products carry
    ``moe_part="experts"`` forward (row outputs) and backward (the weight
    gradients' group outputs) beside their ``robust_stage``.  Compiled,
    the dispatch gathers and the combine's scatters, and their
    transposes in the backward, carry ``moe_part="route"``."""
    from repro.kernels import target

    monkeypatch.setattr(target, "on_tpu", lambda: True)
    step, args = _tiny_step("xla", "mellum2-12b-a2.5b", _TINY_MOE)
    text = step.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    dots = [l for l in text.splitlines() if "ragged_dot" in l]
    fwd = [l for l in dots if re.search(r"-> tensor<\d+x\d+xbf16>", l)]
    bwd = [l for l in dots if re.search(r"-> tensor<\d+x\d+x\d+xbf16>", l)]
    assert fwd and bwd and len(fwd) + len(bwd) == len(dots)
    for line in dots:
        assert 'moe_part = "experts"' in line, line
        assert 'robust_stage = "backward"' in line, line
    assert any("@argsort" in l and 'moe_part = "route"' in l
               for l in text.splitlines())
    compiled = _compiled_step_text("xla", "mellum2-12b-a2.5b")
    for op in ("gather", "scatter"):
        tagged = [l for l in compiled.splitlines()
                  if f" {op}(" in l and 'moe_part="route"' in l]
        # Forward and backward: one of each per layer at the least.
        assert len(tagged) >= 2 * _TINY_MOE["num_layers"], op


def test_moe_tags_change_no_instruction(monkeypatch):
    from repro.models import moe

    tagged = _compiled_step_text("xla", "mellum2-12b-a2.5b")
    assert 'moe_part="experts"' in tagged and 'moe_part="route"' in tagged
    monkeypatch.setattr(moe, "moe_part",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()      # the grouped products' traces are cached
    step, args = _tiny_step("xla", "mellum2-12b-a2.5b", _TINY_MOE)
    plain = step.lower(*args).compile().as_text()
    jax.clear_caches()      # no later trace reuses the untagged ones
    assert "moe_part" not in plain
    # The scopes shift the numbers in instruction names: compare with every
    # name renumbered in order of first appearance.
    assert _renamed(_instructions(tagged)) == _renamed(_instructions(plain))


def _renamed(text):
    names = {}
    return re.sub(r"%[\w.-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


def test_untagged_config_carries_no_moe_part():
    """A config without an expert layer (SmolLM) gets no expert-layer tag:
    its step is the one the stage tests above strip and compare."""
    assert "moe_part" not in _compiled_step_text("xla")


def test_stage_names_are_closed_and_nested_stage_wins():
    with pytest.raises(ValueError, match="unknown stage"):
        with stages.stage("forward"):
            pass

    def fn(x):
        with stages.stage("attack"):
            y = jnp.sin(x)
            with stages.stage("aggregate"):
                z = jnp.cos(y)
        return y, z

    text = jax.jit(fn).lower(jnp.ones(3)).as_text()
    sin = [l for l in text.splitlines() if "stablehlo.sine" in l]
    cos = [l for l in text.splitlines() if "stablehlo.cosine" in l]
    assert sin and 'robust_stage = "attack"' in sin[0]
    assert cos and 'robust_stage = "aggregate"' in cos[0]


# ---------------------------------------------------------------------------
# Program spans on the profiler's clock; compile counters; data.batch.
# ---------------------------------------------------------------------------

def test_runtime_span_lands_on_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with obs_runtime.span("obs.test_span"):
            jnp.ones(4).block_until_ready()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    planes = [plane.name for plane in ProfileData.from_file(path).planes
              if any(ev.name == "obs.test_span"
                     for line in plane.lines for ev in line.events)]
    assert planes and all(p.startswith("/host:") for p in planes)
    assert obs_runtime.history(name="obs.test_span")[-1]["dur"] >= 0.0


def test_compile_counters_count_fresh_compiles_only():
    counted = ("jax.compile_s", "jax.lower_s")
    fn = jax.jit(lambda x: jnp.tanh(x) * 3.0 - 1.0)
    x = jnp.ones(5)
    before = obs_runtime.counters()
    fn(x).block_until_ready()
    after = obs_runtime.counters()
    for name in counted:
        assert after.get(name, 0.0) > before.get(name, 0.0), name
    ev = obs_runtime.history(name="jax.compile")[-1]
    assert ev["args"]["fun_name"] == "jit(<lambda>)"
    assert ev["args"]["seconds"] >= 0.0
    fn(x).block_until_ready()
    again = obs_runtime.counters()
    for name in counted:
        assert again.get(name) == after.get(name), name


def test_worker_batches_records_one_span_per_batch():
    ds = WorkerDataset({"x": np.arange(40.0).reshape(20, 2),
                        "y": np.arange(20) % 4},
                       [np.arange(0, 10), np.arange(10, 20)])
    feed = worker_batches(ds, 3, seed=1)
    mark = obs_runtime.event("obs.test_mark")["seq"]
    for i in range(1, 6):
        batch = next(feed)
        assert batch["x"].shape == (2, 3, 2)
        # The batch's span is closed and recorded before it is handed out.
        spans = [e for e in obs_runtime.history(name="data.batch",
                                                kind="span")
                 if e["seq"] > mark]
        assert len(spans) == i and spans[-1]["dur"] >= 0.0
