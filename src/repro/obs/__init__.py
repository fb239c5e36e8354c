"""Unified telemetry: in-scan health taps + structured runtime tracing.

Three parts, one import surface:

* :mod:`repro.obs.taps` — :class:`HealthTaps`, a pytree of per-round
  robustness diagnostics computed INSIDE the compiled round (riding the
  scan-output metrics transfer; toggled by the owners' static ``taps``
  config flags, which are jit/bucket key material);
* :mod:`repro.obs.runtime` — the process-wide event registry (counters,
  timestamped spans, JSONL + Chrome-trace exporters) that absorbs the
  kernel dispatch ring as a re-export;
* :mod:`repro.obs.stages` — the stage tags of the robust train step
  (``stage(name)``), readable per stage on a device trace.

Importing this package starts :func:`repro.obs.runtime.watch_compiles`:
JAX's trace, lower and compile times feed the registry's counters.
"""
from repro.obs.runtime import (
    DispatchRecord, KernelDecision, Runtime, counters, dispatch_count,
    dispatch_history, event, export_chrome_trace, export_jsonl,
    get_runtime, history, import_jsonl, inc, last_dispatch, reset, snapshot,
    span, watch_compiles,
)
from repro.obs.stages import STAGES, stage
from repro.obs.taps import TAP_FIELDS, HealthTaps, health_taps

watch_compiles()

__all__ = [
    "HealthTaps", "health_taps", "TAP_FIELDS", "STAGES", "stage",
    "Runtime", "get_runtime", "event", "span", "inc", "history",
    "counters", "snapshot", "reset", "export_jsonl", "export_chrome_trace",
    "import_jsonl", "watch_compiles",
    "DispatchRecord", "KernelDecision", "dispatch_count",
    "dispatch_history", "last_dispatch",
]
