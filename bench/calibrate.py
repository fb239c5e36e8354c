"""Readings that the limits of ``correct`` are set from, on the chip.

  python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 [--out f.json]

For each seed: the program's first steps through its compiled train step
(sound runs: the lower readings), and against the float32 reference the
same numbers for the control (the reference at float8 products, one
precision below the configuration's bfloat16) and for planted faults: the
reference with half of every worker's batch left out, and the program's
losses altered by 5% where the step returns them.  One process, one
compile of the program's step.  Prints one line per seed and reading and,
last, a JSON summary: per number, the largest sound reading and the
smallest control and fault readings, and the limits they give (see
:func:`limits`).  ``--out`` also keeps every run's raw per-leaf readings,
so that a number can be recomputed from them.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def _plain(read: dict) -> dict:
    return {k: np.asarray(v).tolist() if isinstance(v, np.ndarray) else v
            for k, v in read.items()}


#: A step that returns its state unchanged reads 1 on the change gap.
UNCHANGED = {"change_gap": 1.0}


def limits(summary: dict) -> dict:
    """{number: (lower, upper, limit)}.  The lower reading is the largest
    sound one; the upper the smallest of the control's (where it reads at
    least 3x the lower), each fault's (at least 10x) and the unchanged
    state's (at least 3x).  The limit lies two thirds of the way from the
    lower to the upper on a log scale, to two significant digits: room on
    both sides, more of it above the lower.  None where no upper exists."""
    out = {}
    for k, lower in summary["sound"].items():
        uppers = [summary["control"][k]] \
            if summary["control"][k] >= 3 * lower else []
        uppers += [summary[f][k] for f in ("half_batch", "answer_altered")
                   if summary[f][k] >= 10 * lower]
        if UNCHANGED.get(k, 0.0) >= 3 * lower:
            uppers.append(UNCHANGED[k])
        if not uppers:
            out[k] = (lower, None, None)
            continue
        upper = min(uppers)
        limit = float(f"{lower ** (1 / 3) * upper ** (2 / 3):.2g}")
        out[k] = (lower, upper, limit)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # not /tmp/tpu_logs
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from harness import compare, program, reference
    from harness.cells import find_cell
    from harness.traffic import worker_feed

    cell = find_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: no TPU")
    traffic, sizes = cell.traffic, cell.config["sizes"]
    seeds = [int(s) for s in args.seeds.split(",")]
    kinds = ("sound", "control", "half_batch", "answer_altered")
    readings = {k: [] for k in kinds}
    raw = []
    step = prog = None
    for seed in seeds:
        t = time.perf_counter()
        feed = worker_feed(traffic, sizes["vocab_size"], sizes["d_model"],
                           seed)
        key = program.seed_key(seed)
        if prog is None:
            prog = program.build(cell.config, traffic, feed, seed=seed)
        state = prog.init(key)
        key, sub = jax.random.split(key)
        batch = next(feed)
        if step is None:
            step = prog.step.lower(state, batch, sub).compile()
            print(program.check_dispatch(require_compiled=True), flush=True)
        state, key, got, batches = program.first_steps(
            step, state, key, sub, batch, feed, prog.leaf_paths,
            beta=float(traffic["beta"]), compared=3)
        del state
        t_prog = time.perf_counter() - t
        rkey = program.seed_key(seed)
        t = time.perf_counter()
        ref = reference.run(cell.reference, sizes, traffic, rkey, batches)
        t_ref = time.perf_counter() - t
        runs = {
            "sound": got,
            "control": reference.run(cell.reference, sizes, traffic, rkey,
                                     batches, mode="fp8"),
            "half_batch": reference.run(cell.reference, sizes, traffic,
                                        rkey, batches, half_batch=True),
            "answer_altered": dict(got, loss=[x * 1.05 for x in got["loss"]]),
        }
        raw.append({"seed": seed, "reference": _plain(ref),
                    **{k: _plain(r) for k, r in runs.items()
                       if k != "answer_altered"}})
        for name, r in runs.items():
            vals = compare.numbers(r, ref)
            readings[name].append(vals)
            print(f"seed {seed} {name} {json.dumps(vals)}", flush=True)
        print(f"seed {seed} widest change gaps "
              f"{compare.leaf_report(got, ref)!r}", flush=True)
        print(f"seed {seed} program_s {t_prog!r} reference_s {t_ref!r} "
              f"ref_loss {ref['loss']!r} prog_loss {got['loss']!r} "
              f"ref_dnorm {ref['direction_norm']!r} prog_dnorm "
              f"{got['direction_norm']!r} nnm_margin {ref['nnm_margin']!r}",
              flush=True)
    summary = {"workload": args.workload, "seeds": seeds,
               "readings": readings}
    for name in kinds:
        agg = max if name == "sound" else min
        summary[name] = {k: agg(v[k] for v in readings[name])
                         for k in compare.NUMBERS}
    summary["limits"] = limits(summary)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(dict(summary, raw=raw)) + "\n")
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
