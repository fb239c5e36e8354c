"""Process-wide runtime event registry: counters + timestamped spans.

Runtime telemetry used to be scattered — one-shot
``kernels.dispatch.last_dispatch()``, ad-hoc ``RoundEngine.trace_count``
counters, prints in benchmarks.  This module is the single sink: every
owner (round engine, fleet runner/service, serve engine, checkpoint
writer, kernel dispatch) emits **instant events** (:func:`event`),
**spans** (:func:`span`, wall-clock begin/duration) and **counters**
(:func:`inc`) into one bounded ring, queryable as :func:`history` and
exportable as JSONL (:func:`export_jsonl`) or the Chrome trace-event
format (:func:`export_chrome_trace` — loadable in Perfetto /
``chrome://tracing``).

Design constraints:

* **host-side only** — emission happens in Python (at trace time for
  anything inside jit, per the dispatch-record semantics), never inside
  compiled programs; the compiled hot path is untouched;
* **on the profiler's clock too** — every :func:`span` also enters a
  ``jax.profiler.TraceAnnotation`` of its name, so under
  ``jax.profiler.trace`` it lands on the profile's host plane beside the
  device ops (``span_at`` is retroactive and stays ring-only);
* **bounded** — the ring holds the most recent ``capacity`` events
  (default 4096); counters are plain monotone floats;
* **no hard deps** — stdlib only at import (JAX is imported lazily, for
  the profiler annotation and :func:`watch_compiles`); numpy / dataclass
  payloads are sanitized lazily at snapshot/export time, so emitting is
  cheap.

:func:`watch_compiles` (called once on import of :mod:`repro.obs`) feeds
JAX's own compile events into the counters ``jax.lower_s``,
``jax.compile_s``, ``jax.cache_hits`` and ``jax.cache_misses`` and one
``jax.compile`` event per backend compile.

The kernel dispatch ring (:class:`DispatchRecord`, history, head) is
re-exported here at the bottom: ``obs.runtime`` is the one-stop querying
surface, ``kernels.dispatch`` stays the owner (no import cycle — dispatch
only imports this module lazily inside ``open_record``).
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Any, Iterator, Optional

#: Default ring capacity (events, not bytes).
DEFAULT_CAPACITY = 4096

#: ``jax.profiler.TraceAnnotation`` once looked up; False without JAX.
_annotation_cls: Any = None


def _profiler_annotation(name: str):
    """A context that marks ``name`` on the JAX profiler's host plane
    (a no-op when no profile is being taken, or without JAX)."""
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = False
        _annotation_cls = TraceAnnotation
    return _annotation_cls(name) if _annotation_cls else nullcontext()


def _sanitize(value: Any) -> Any:
    """JSON-able deep copy: dataclasses -> dicts, numpy scalars -> Python
    scalars, anything else -> ``str``."""
    if dataclasses.is_dataclass(value) and not isinstance(value, type):
        return _sanitize(dataclasses.asdict(value))
    if isinstance(value, dict):
        return {str(k): _sanitize(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_sanitize(v) for v in value]
    if value is None or isinstance(value, (bool, int, float, str)):
        return value
    item = getattr(value, "item", None)     # numpy scalar without importing
    if item is not None and getattr(value, "ndim", None) in (0, None):
        try:
            return _sanitize(item())
        except (TypeError, ValueError):
            pass
    return str(value)


class Runtime:
    """One bounded event ring + counter table.  Thread-safe appends (the
    fleet service and a checkpoint writer may interleave)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._capacity = capacity
        self._events: deque = deque(maxlen=capacity)
        self._counters: dict[str, float] = {}
        self._epoch = time.perf_counter()
        self._seq = 0                   # lifetime emitted (ring may drop)

    # -- clock ------------------------------------------------------------
    def _now(self) -> float:
        return time.perf_counter() - self._epoch

    def now(self) -> float:
        """Seconds since the registry epoch — the timebase every ring
        event's ``ts`` uses.  Callers stash this to later reconstruct
        spans whose endpoints they only learn after the fact
        (:meth:`span_at`)."""
        return self._now()

    # -- emission ---------------------------------------------------------
    def event(self, name: str, **args: Any) -> dict:
        """Record an instant event; returns the (live) event dict."""
        ev = {"name": name, "kind": "instant", "ts": self._now(),
              "dur": None, "args": args}
        with self._lock:
            self._seq += 1
            ev["seq"] = self._seq
            self._events.append(ev)
        return ev

    @contextmanager
    def span(self, name: str, **args: Any) -> Iterator[dict]:
        """Record a wall-clock span around a ``with`` block.  The event is
        appended at EXIT (so ``dur`` is final); ``ts`` is the entry time.
        The block is also a ``jax.profiler.TraceAnnotation`` of ``name``."""
        t0 = self._now()
        ev = {"name": name, "kind": "span", "ts": t0, "dur": None,
              "args": args}
        try:
            with _profiler_annotation(name):
                yield ev
        finally:
            ev["dur"] = self._now() - t0
            with self._lock:
                self._seq += 1
                ev["seq"] = self._seq
                self._events.append(ev)

    def span_at(self, name: str, start: float, end: Optional[float] = None,
                **args: Any) -> dict:
        """Record a span with EXPLICIT endpoints (values from
        :meth:`now`), for intervals that aren't a ``with`` block — e.g.
        the fleet service's submit->done job spans, whose start happened
        turns ago in ``submit()``.  ``end=None`` means "now"."""
        t1 = self._now() if end is None else end
        ev = {"name": name, "kind": "span", "ts": start,
              "dur": max(t1 - start, 0.0), "args": args}
        with self._lock:
            self._seq += 1
            ev["seq"] = self._seq
            self._events.append(ev)
        return ev

    def inc(self, name: str, value: float = 1.0) -> float:
        """Bump a monotone counter; returns the new value."""
        with self._lock:
            new = self._counters.get(name, 0.0) + value
            self._counters[name] = new
            return new

    # -- querying ---------------------------------------------------------
    def history(self, *, limit: Optional[int] = None,
                name: Optional[str] = None,
                kind: Optional[str] = None) -> list[dict]:
        """Most recent events, oldest first, optionally filtered by exact
        ``name`` and/or ``kind`` ("instant" | "span")."""
        with self._lock:
            evs = list(self._events)
        if name is not None:
            evs = [e for e in evs if e["name"] == name]
        if kind is not None:
            evs = [e for e in evs if e["kind"] == kind]
        return evs if limit is None else evs[-limit:]

    def counters(self) -> dict[str, float]:
        with self._lock:
            return dict(self._counters)

    def snapshot(self) -> list[dict]:
        """Sanitized (JSON-able) copy of the full ring, oldest first."""
        return [dict(e, args=_sanitize(e["args"])) for e in self.history()]

    def reset(self, capacity: Optional[int] = None) -> None:
        """Drop all events and counters; restart the clock."""
        with self._lock:
            if capacity is not None:
                self._capacity = capacity
            self._events = deque(maxlen=self._capacity)
            self._counters = {}
            self._epoch = time.perf_counter()
            self._seq = 0

    # -- exporters --------------------------------------------------------
    def export_jsonl(self, path: str) -> int:
        """One JSON object per line: every ring event (sanitized), then one
        ``kind="counter"`` line per counter.  Returns the line count."""
        events = self.snapshot()
        counters = self.counters()
        now = self._now()
        with open(path, "w") as fh:
            for ev in events:
                fh.write(json.dumps(ev, sort_keys=True) + "\n")
            for cname in sorted(counters):
                fh.write(json.dumps(
                    {"name": cname, "kind": "counter", "ts": now,
                     "value": counters[cname]}, sort_keys=True) + "\n")
        return len(events) + len(counters)

    def export_chrome_trace(self, path: str) -> int:
        """Chrome trace-event JSON (Perfetto / ``chrome://tracing``):
        spans as complete ("X") events, instants as "i", counters as one
        "C" sample each.  Timestamps are microseconds since the registry
        epoch, emitted in nondecreasing order.  Returns the event count."""
        pid = os.getpid()
        rows = []
        for ev in self.snapshot():
            row = {"name": ev["name"], "pid": pid, "tid": 0,
                   "ts": ev["ts"] * 1e6, "args": ev["args"]}
            if ev["kind"] == "span":
                row["ph"] = "X"
                row["dur"] = (ev["dur"] or 0.0) * 1e6
            else:
                row["ph"] = "i"
                row["s"] = "p"
            rows.append(row)
        now_us = self._now() * 1e6
        for cname, val in sorted(self.counters().items()):
            rows.append({"name": cname, "ph": "C", "pid": pid, "tid": 0,
                         "ts": now_us, "args": {"value": val}})
        rows.sort(key=lambda r: r["ts"])
        with open(path, "w") as fh:
            json.dump({"traceEvents": rows, "displayTimeUnit": "ms"}, fh)
        return len(rows)


def import_jsonl(path: str) -> list[dict]:
    """Parse a :func:`export_jsonl` file back into its line dicts."""
    out = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


# ---------------------------------------------------------------------------
# The process singleton + module-level facade (what callers import).
# ---------------------------------------------------------------------------

_RUNTIME = Runtime()


def get_runtime() -> Runtime:
    return _RUNTIME


def event(name: str, **args: Any) -> dict:
    return _RUNTIME.event(name, **args)


def span(name: str, **args: Any):
    return _RUNTIME.span(name, **args)


def span_at(name: str, start: float, end: Optional[float] = None,
            **args: Any) -> dict:
    return _RUNTIME.span_at(name, start, end, **args)


def now() -> float:
    return _RUNTIME.now()


def inc(name: str, value: float = 1.0) -> float:
    return _RUNTIME.inc(name, value)


def history(*, limit: Optional[int] = None, name: Optional[str] = None,
            kind: Optional[str] = None) -> list[dict]:
    return _RUNTIME.history(limit=limit, name=name, kind=kind)


def counters() -> dict[str, float]:
    return _RUNTIME.counters()


def snapshot() -> list[dict]:
    return _RUNTIME.snapshot()


def reset(capacity: Optional[int] = None) -> None:
    _RUNTIME.reset(capacity=capacity)


def export_jsonl(path: str) -> int:
    return _RUNTIME.export_jsonl(path)


def export_chrome_trace(path: str) -> int:
    return _RUNTIME.export_chrome_trace(path)


# ---------------------------------------------------------------------------
# JAX's compile events -> counters + one ring event per backend compile.
# ---------------------------------------------------------------------------

#: JAX monitoring events timed into ``jax.lower_s``: tracing to a jaxpr
#: and lowering it to an MLIR module (``jax._src.dispatch``).
_LOWER_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")
#: Timed into ``jax.compile_s``: one XLA compile, or one load from the
#: persistent compilation cache.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: The persistent cache's answers: (answer, counter).
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": ("hit", "jax.cache_hits"),
    "/jax/compilation_cache/cache_misses": ("miss", "jax.cache_misses")}
_watch_lock = threading.Lock()
_watching = False


class _CompileWatch(threading.local):
    """Per-thread state of the compile listeners: how deep in nested
    lowering events the thread is (a jit traced inside another's trace
    is counted once, in the outer one), and the persistent cache's answer
    for the backend compile in progress."""
    depth = 0
    cache: Optional[str] = None


def watch_compiles() -> None:
    """Register listeners on ``jax.monitoring``, once per process.

    They feed the counters ``jax.lower_s`` (seconds tracing and lowering
    top-level jits), ``jax.compile_s`` (seconds in backend compiles,
    cache loads included), ``jax.cache_hits`` and ``jax.cache_misses``
    (the persistent compilation cache's answers; a miss is counted when
    the compiled program is written), and record one ``jax.compile``
    event per backend compile with its ``fun_name``, ``seconds`` and
    ``cache`` ("hit", "miss", or None where the persistent cache is off).
    """
    global _watching
    with _watch_lock:
        if _watching:
            return
        _watching = True
    from jax import monitoring

    watch = _CompileWatch()

    def on_enter(event: str, value: float, **kwargs: Any) -> None:
        if event in _LOWER_EVENTS:
            watch.depth += 1
        elif event == _COMPILE_EVENT:
            watch.cache = None

    def on_duration(event: str, duration: float, **kwargs: Any) -> None:
        if event in _LOWER_EVENTS:
            watch.depth = max(watch.depth - 1, 0)
            if watch.depth == 0:
                inc("jax.lower_s", duration)
        elif event == _COMPILE_EVENT:
            inc("jax.compile_s", duration)
            _RUNTIME.event("jax.compile", fun_name=kwargs.get("fun_name"),
                           seconds=duration, cache=watch.cache)

    def on_event(event: str, **kwargs: Any) -> None:
        if event in _CACHE_EVENTS:
            watch.cache, counter = _CACHE_EVENTS[event]
            inc(counter)

    monitoring.register_scalar_listener(on_enter)
    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


# ---------------------------------------------------------------------------
# Kernel dispatch ring re-exports: obs.runtime is the query surface, the
# ring itself lives with its owner (repro.kernels.dispatch), which imports
# THIS module lazily — strictly one-way at import time, no cycle.
# ---------------------------------------------------------------------------

from repro.kernels.dispatch import (   # noqa: E402  (intentional tail import)
    DispatchRecord, KernelDecision, dispatch_count, dispatch_history,
    last_dispatch,
)

__all__ = [
    "DEFAULT_CAPACITY", "Runtime", "get_runtime",
    "event", "span", "span_at", "now", "inc", "history", "counters",
    "snapshot", "reset",
    "export_jsonl", "export_chrome_trace", "import_jsonl", "watch_compiles",
    "DispatchRecord", "KernelDecision", "dispatch_count",
    "dispatch_history", "last_dispatch",
]
