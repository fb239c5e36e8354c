"""From a profiler trace (``.xplane.pb``) to device busy time, time per
kernel and idle gaps named by what the host was doing.

Device operations are the events of the ``XLA Ops`` line of each TPU
plane.  Host spans are the benchmark's own ``TraceAnnotation`` events on
the host plane.  Both are on the profiler's one clock.

  python3 bench/harness/trace.py <trace.xplane.pb> <out.txt>

writes what one reads by hand before matching a new kernel's name.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Iterable, Optional

OPS_LINE = "XLA Ops"
#: What the text of a Pallas kernel's device op holds.
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
#: Stats of a device op whose text names the source of the op.
NAME_STATS = ("long_name", "tf_op", "hlo_op", "name")


@dataclasses.dataclass
class Op:
    name: str             # the HLO instruction's name, e.g. %gram_pallas.3
    start_ns: float
    dur_ns: float
    text: str             # the event's full text, for pattern matches
    parent: bool = False  # holds other ops (a while loop around its body)


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Reduction:
    devices: int
    window_ns: tuple          # (start, end) of the traced window
    ops: list                 # Op, clipped to the window, all devices
    spans: list               # Span, host
    busy_ns: float            # union of op intervals, mean over devices
    gaps: list                # (start, end) idle intervals, device 0

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def kernel_s(self, patterns: Iterable[str]) -> Optional[float]:
        """Summed device seconds of the ops whose name holds any of
        ``patterns``, per device; None when no op matches.  (The name, not
        the whole text: an op's text also names its operands.)"""
        pats = tuple(patterns)
        return self._sum(lambda o: any(p in o.name for p in pats))

    def custom_calls_s(self) -> Optional[float]:
        """Summed device seconds of every Pallas kernel (a TPU custom
        call), per device; None when there is none."""
        return self._sum(lambda o: CUSTOM_CALL in o.text)

    def _sum(self, pick) -> Optional[float]:
        hit = [o.dur_ns for o in self.ops if not o.parent and pick(o)]
        return sum(hit) * 1e-9 / self.devices if hit else None

    def top_ops(self, k: int = 10) -> list:
        """[[op name, seconds], ...] of the ops that took most time."""
        tot: dict = defaultdict(float)
        for o in self.ops:
            if not o.parent:
                tot[o.name] += o.dur_ns * 1e-9 / self.devices
        return [[n, s] for n, s in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def idle_by_span(self, k: int = 10) -> list:
        """[[host span, seconds], ...] of the longest idle gaps, each named
        by the host span that covers most of it ("other" if none)."""
        named = []
        for a, b in self.gaps:
            best, cover = "other", 0.0
            for s in self.spans:
                c = min(b, s.end_ns) - max(a, s.start_ns)
                if c > cover:
                    best, cover = s.name, c
            named.append([best, (b - a) * 1e-9])
        return sorted(named, key=lambda x: -x[1])[:k]

    def span_s(self, name: str) -> list:
        """Durations in seconds of the host spans called ``name``."""
        return [(s.end_ns - s.start_ns) * 1e-9 for s in self.spans
                if s.name == name]


def find_trace(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise SystemExit(f"expected one trace under {log_dir}, "
                         f"found {found}")
    return found[0]


def _stats(ev) -> dict:
    out = {}
    for kv in ev.stats:
        try:
            k, v = kv
        except (TypeError, ValueError):
            continue
        out[str(k)] = v
    return out


def _merge(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(path: str, span_names: Iterable[str], window_span: str
           ) -> Reduction:
    """Reduce the trace at ``path``.  The traced window runs from the
    start of the first host span ``window_span`` to the end of the last;
    device ops are clipped to it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    names = set(span_names) | {window_span}
    spans: list = []
    per_dev: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    st = _stats(ev)
                    text = " ".join([ev.name] + [str(st[k]) for k in
                                                 NAME_STATS if k in st])
                    ops.append(Op(ev.name.split(" = ")[0], ev.start_ns,
                                  ev.duration_ns, text))
            if ops:
                ops.sort(key=lambda o: o.start_ns)
                for a, b in zip(ops, ops[1:]):
                    a.parent = b.start_ns < a.start_ns + a.dur_ns
                per_dev.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        spans.append(Span(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
    steps = [s for s in spans if s.name == window_span]
    if not per_dev:
        raise SystemExit("the trace holds no device operations")
    if not steps:
        raise SystemExit(f"the trace holds no {window_span!r} span")
    lo = min(s.start_ns for s in steps)
    hi = max(s.end_ns for s in steps)
    clipped, busy, gaps = [], 0.0, []
    for i, ops in enumerate(per_dev):
        dev = []
        for o in ops:
            a, b = max(o.start_ns, lo), min(o.start_ns + o.dur_ns, hi)
            if b > a:
                dev.append(Op(o.name, a, b - a, o.text, o.parent))
        merged = _merge([[o.start_ns, o.start_ns + o.dur_ns] for o in dev])
        busy += sum(b - a for a, b in merged)
        if i == 0:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges) - 1, 2)
                    if edges[j + 1] > edges[j]]
        clipped.extend(dev)
    return Reduction(devices=len(per_dev), window_ns=(lo, hi), ops=clipped,
                     spans=[s for s in spans if s.name != window_span],
                     busy_ns=busy / len(per_dev), gaps=gaps)


def dump(path: str, out_path: str, k: int = 60) -> None:
    """Write the planes, lines and the longest device ops with all their
    stats to ``out_path``: what one reads by hand before matching names."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    with open(out_path, "w") as fh:
        for plane in pd.planes:
            lines = list(plane.lines)
            fh.write(f"PLANE {plane.name}: {[l.name for l in lines]}\n")
            for line in lines:
                evs = list(line.events)
                if not evs:
                    continue
                fh.write(f"  LINE {line.name}: {len(evs)} events, "
                         f"first {evs[0].start_ns} last "
                         f"{evs[-1].start_ns + evs[-1].duration_ns}\n")
                for ev in sorted(evs, key=lambda e: -e.duration_ns)[:k]:
                    fh.write(f"    {ev.duration_ns:.0f} ns {ev.name!r} "
                             f"{_stats(ev)!r}\n")


if __name__ == "__main__":
    import sys
    dump(sys.argv[1], sys.argv[2])
