"""The stage readers on a synthetic trace reduction whose op texts carry
the program's stage tags, and the host readers on the program's event
ring and counters."""
from types import SimpleNamespace

import pytest

from harness import stages
from harness.cells import metric_reader
from harness.trace import CUSTOM_CALL, Op, Reduction

STAGE_METRICS = {"backward_ms": "backward", "momentum_ms": "momentum",
                 "attack_ms": "attack", "aggregate_xla_ms": "aggregate",
                 "kappa_ms": "kappa", "optimizer_ms": "optimizer"}
STEPS = 2


def _op(name, start, dur, stage=None, kernel=False, parent=False):
    attrs = []
    if kernel:
        attrs.append("kernel_metadata={}")
    if stage:
        attrs.append(f'robust_stage="{stage}"')
    text = f"%{name} = f32[8]{{0}} fusion(%p), kind=kLoop"
    if kernel:
        text = (f"%{name} = f32[8]{{0}} custom-call(%p), "
                f"{CUSTOM_CALL}")
    if attrs:
        text += ", frontend_attributes={" + ",".join(attrs) + "}"
    return Op(f"%{name}", float(start), float(dur), text, parent)


def _reduction(ops, devices=1):
    busy = sum(o.dur_ns for o in ops if not o.parent) / devices
    return Reduction(devices=devices, window_ns=(0.0, 1e9), ops=ops,
                     spans=[], busy_ns=busy, gaps=[])


def _tagged_ops(offset=0.0):
    """One device's ops of two steps, none overlapping: every stage,
    the two kernels (tagged aggregate), a loop around the backward and an
    untagged copy.  Durations in ns."""
    ops = [_op("while.1", offset, 6e6, "backward", parent=True)]
    t = offset
    for stage, dur in [("backward", 3e6), ("backward", 2e6),
                       ("momentum", 4e6), ("attack", 1e6),
                       ("aggregate", 5e5), ("kappa", 7e5),
                       ("optimizer", 3e5), (None, 9e5)]:
        ops.append(_op(f"fusion.{len(ops)}", t, dur, stage))
        t += dur
    ops.append(_op("gram_pallas.1", t, 8e6, "aggregate", kernel=True))
    ops.append(_op("mixtrim_pallas.1", t + 8e6, 6e6, "aggregate",
                   kernel=True))
    return ops


def _ctx(ops, devices=1):
    return SimpleNamespace(trace=_reduction(ops, devices), steps=STEPS)


def _read(name, ctx):
    return metric_reader(name).read(ctx)


@pytest.mark.parametrize("devices", [1, 2])
def test_stage_metrics_read_their_tagged_ops(devices):
    ops = [o for d in range(devices) for o in _tagged_ops(d * 1e3)]
    ctx = _ctx(ops, devices)
    want = {"backward": 5.0, "momentum": 4.0, "attack": 1.0,
            "aggregate": 0.5, "kappa": 0.7, "optimizer": 0.3}
    for metric, stage in STAGE_METRICS.items():
        assert _read(metric, ctx) == pytest.approx(want[stage] / STEPS)
    # The kernels are not in aggregate_xla_ms, nor the loop in backward.
    assert _read("unstaged_ms", ctx) == pytest.approx(0.9 / STEPS)


def test_stages_and_unstaged_add_up_to_xla_ms():
    ctx = _ctx(_tagged_ops())
    parts = [_read(m, ctx) for m in STAGE_METRICS] + \
        [_read("unstaged_ms", ctx)]
    assert sum(parts) == pytest.approx(_read("xla_ms", ctx))


def test_absent_stage_reads_zero_untagged_trace_reads_none():
    ops = [o for o in _tagged_ops() if 'robust_stage="attack"' not in o.text]
    assert _read("attack_ms", _ctx(ops)) == 0.0
    plain = [Op(o.name, o.start_ns, o.dur_ns,
                o.text.split(", frontend_attributes")[0], o.parent)
             for o in _tagged_ops()]
    ctx = _ctx(plain)
    for metric in list(STAGE_METRICS) + ["unstaged_ms"]:
        assert _read(metric, ctx) is None, metric
    # A tag held only by a loop around the ops does not count.
    loop_only = [_op("while.1", 0, 6e6, "backward", parent=True)] + plain[1:]
    assert stages.stage_ms(_ctx(loop_only), "backward") is None


def test_host_readers_read_the_program_ring_and_counters():
    from repro.obs import runtime

    runtime.reset()
    ctx = SimpleNamespace()
    for name in ("sample_ms", "lower_s", "compile_s"):
        assert _read(name, ctx) is None, name
    for dur in (0.002, 0.004):
        runtime.get_runtime().span_at("data.batch", 0.0, dur)
    runtime.inc("jax.lower_s", 1.5)
    runtime.inc("jax.compile_s", 2.5)
    assert _read("sample_ms", ctx) == pytest.approx(3.0)
    assert _read("lower_s", ctx) == 1.5
    assert _read("compile_s", ctx) == 2.5
    runtime.reset()
