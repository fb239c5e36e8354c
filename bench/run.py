"""Benchmark of the robust D-SHB train step on the chip.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  One
run builds the program's jitted train step for them (weights from the seed
on the device, zero momenta, the state donated), compiles it for the
cell's one batch shape, and drives it through its first steps from the
seed, keeping what the check of ``correct`` needs.  That is set-up.  Then
the window: next batch, step, fetch the step's scalars, for ``--seconds``
seconds.  Afterwards, with the program's state freed, the plain reference
repeats the first steps and the numbers are compared with their limits.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profile of a few steady steps inside the
window.  The last line of standard output is one JSON object; the numbers
compared are also the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: Steps of set-up that the reference repeats and the check compares.
COMPARED_STEPS = 3
#: Window steps before the profiler starts, and steps it records.
TRACE_WARM, TRACE_STEPS = 1, 3
#: Host spans of one window step.
SPANS = ("bench.input", "bench.step", "bench.fetch")
WINDOW_SPAN = "bench.window_step"


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def device_info(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        fail(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def hbm_bytes(compiled) -> int:
    """Device bytes of the compiled step: arguments + outputs +
    temporaries - the outputs aliased to donated arguments."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


def finite(metrics: dict) -> bool:
    return all(math.isfinite(float(v)) for v in metrics.values()
               if getattr(v, "ndim", 1) == 0)


def run_cell(args: argparse.Namespace, *, root: Path = ROOT,
             require_tpu: bool = True, bench: dict = None,
             loss_wrap=None, step_wrap=None, t0: float = None) -> dict:
    """One run of one cell; returns the result object.  ``bench``,
    ``loss_wrap`` and ``step_wrap`` serve the tests: a benchmark file of
    their own and a fault planted under the timed path."""
    from harness.cells import find_cell, metric_reader

    t0 = T0 if t0 is None else t0
    cell = find_cell(args.workload, root=root, bench=bench)
    devs = device_info(cell.chips, require_tpu)

    import jax
    from harness import compare, costs, program, reference
    from harness.trace import find_trace, reduce
    from harness.traffic import tokens_per_step, worker_feed

    traffic, sizes = cell.traffic, cell.config["sizes"]
    feed = worker_feed(traffic, sizes["vocab_size"], sizes["d_model"],
                       args.seed)
    phases = {"start": time.perf_counter() - t0}
    prog = program.build(cell.config, traffic, feed, seed=args.seed,
                         loss_wrap=loss_wrap, step_wrap=step_wrap)
    key = program.seed_key(args.seed)
    state = prog.init(key)
    key, sub = jax.random.split(key)
    batch = next(feed)
    jax.block_until_ready(state)
    phases["data_and_init"] = time.perf_counter() - t0
    lowered = prog.step.lower(state, batch, sub)
    phases["trace"] = time.perf_counter() - t0
    step = lowered.compile()
    phases["compile"] = time.perf_counter() - t0
    dispatch = program.check_dispatch(require_compiled=require_tpu)
    step_hbm = hbm_bytes(step)

    # Set-up steps: the window's own call and feed; what the reference
    # repeats is kept.
    state, key, prog_read, compared = program.first_steps(
        step, state, key, sub, batch, feed, prog.leaf_paths,
        beta=float(traffic["beta"]), compared=COMPARED_STEPS)
    setup_s = time.perf_counter() - t0

    # The window.
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    steps = failed = 0
    tracing = False
    t_start = time.perf_counter()
    while True:
        if args.trace and steps == TRACE_WARM:
            jax.profiler.start_trace(trace_dir)
            tracing = True
        ann = jax.profiler.StepTraceAnnotation(WINDOW_SPAN, step_num=steps) \
            if tracing else contextlib.nullcontext()
        with ann:
            with jax.profiler.TraceAnnotation(SPANS[0]):
                key, sub = jax.random.split(key)
                batch = jax.device_put(next(feed))
            with jax.profiler.TraceAnnotation(SPANS[1]):
                state, metrics = step(state, batch, sub)
            with jax.profiler.TraceAnnotation(SPANS[2]):
                m = jax.device_get(metrics)
        steps += 1
        failed += not finite(m)
        if tracing and steps == TRACE_WARM + TRACE_STEPS:
            jax.profiler.stop_trace()
            tracing = False
        if (time.perf_counter() - t_start >= args.seconds
                and (not args.trace or steps >= TRACE_WARM + TRACE_STEPS)):
            break
    window_s = time.perf_counter() - t_start
    jax.block_until_ready(state)
    memory_peak = peak_bytes(devs)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": False, "attempted": steps, "failed": failed}
    if args.trace:
        red = reduce(find_trace(trace_dir), SPANS, WINDOW_SPAN)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        # What a per-layer metric reader reads.
        ctx = SimpleNamespace(cell=cell, trace=red, steps=TRACE_STEPS,
                              peak=costs.peaks(devs[0].device_kind),
                              leaf_widths=prog.leaf_widths, chips=len(devs))
        metrics_out = {}
        for entry in cell.per_layer:
            value = metric_reader(entry["name"], root=root).read(ctx)
            if value is not None:
                metrics_out[entry["name"]] = {"value": value,
                                              "unit": entry["unit"]}
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": red.idle_by_span(10)}
    else:
        e2e = {"setup_s": setup_s,
               "tokens_per_s": steps * tokens_per_step(traffic) / window_s,
               "step_hbm_gb": step_hbm / 1e9}
        metrics_out = {e["name"]: {"value": e2e[e["name"]], "unit": e["unit"]}
                       for e in cell.end_to_end}
    result["metrics"] = metrics_out
    result["device"] = device

    # The check: the program's state freed, the reference repeats the
    # compared steps from the seed.
    del state, step, metrics, batch, prog
    gc.collect()
    t_ref = time.perf_counter()
    ref_read = reference.run(cell.reference, sizes, traffic,
                             program.seed_key(args.seed), compared)
    ref_s = time.perf_counter() - t_ref
    values = compare.numbers(prog_read, ref_read)
    ok, checks = compare.verdict(values, cell.limits)
    result["correct"] = bool(ok and failed == 0)
    result["checks"] = checks
    print(f"dispatch: {dispatch}", file=sys.stderr)
    print(f"setup_s {setup_s!r} window_s {window_s!r} steps {steps} "
          f"reference_s {ref_s!r} step_hbm_bytes {step_hbm}",
          file=sys.stderr)
    print(f"set-up phases (s since start): {phases!r}", file=sys.stderr)
    print(f"losses program {prog_read['loss']!r} reference "
          f"{ref_read['loss']!r}; direction norms program "
          f"{prog_read['direction_norm']!r} reference "
          f"{ref_read['direction_norm']!r}; reference NNM margins "
          f"{ref_read['nnm_margin']!r}", file=sys.stderr)
    print(f"widest change gaps [leaf, program, reference, gap]: "
          f"{compare.leaf_report(prog_read, ref_read)!r}", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> None:
    args = parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # not /tmp/tpu_logs
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run_cell(args)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
