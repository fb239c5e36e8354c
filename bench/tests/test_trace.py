"""The trace reduction on a small trace recorded on one TPU v5e: three
steps of a jitted program holding the Gram and mix + trim Pallas kernels
and a bf16 matmul, with the benchmark's host spans around each step."""
from pathlib import Path

import pytest

from harness import costs, trace

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
SPANS = ("bench.input", "bench.step", "bench.fetch")


@pytest.fixture(scope="module")
def red():
    return trace.reduce(str(DATA), SPANS, "bench.window_step")


def test_window_and_busy(red):
    assert red.devices == 1
    assert red.window_s == pytest.approx(0.11396207, rel=1e-9)
    assert red.busy_s == pytest.approx(0.000237365, rel=1e-9)
    idle = sum(b - a for a, b in red.gaps) * 1e-9
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-9)


def test_kernels_by_name(red):
    gram = red.kernel_s(costs.KERNELS["gram"])
    mixtrim = red.kernel_s(costs.KERNELS["mixtrim"])
    assert gram == pytest.approx(8.9135e-05, rel=1e-9)
    assert mixtrim == pytest.approx(9.6802e-05, rel=1e-9)
    # Both kernels are Pallas custom calls; nothing else in it is.
    assert red.custom_calls_s() == pytest.approx(gram + mixtrim, rel=1e-9)
    assert red.kernel_s(("%no_such_kernel",)) is None


def test_host_spans_and_breakdown(red):
    for name in SPANS:
        assert len(red.span_s(name)) == 3
    top = red.top_ops(3)
    assert [n for n, _ in top[:2]] == ["%mixtrim_pallas.1", "%gram_pallas.1"]
    gaps = red.idle_by_span(10)
    assert len(gaps) == 10 and gaps[0][0] == "bench.input"
    assert gaps[0][1] == pytest.approx(0.102393873, rel=1e-6)
