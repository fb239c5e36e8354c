"""Paper Remark 1: aggregation cost scaling in n and d, plus the kernel
backend comparison.

Times each rule (with and without NNM) on dense stacks, then runs the SAME
``robust_aggregate`` pipeline on ``backend="xla"`` vs ``backend="pallas"``
per rule.  Off-TPU the Pallas kernels execute in interpret mode: those
rows are structural checks, not hardware numbers — they are tagged
``interpret=1`` in the CSV, suffixed ``_interp``, and quarantined under
the ``"interpret"`` key of the JSON summary so ``scripts/perf_gate.py``
can never ingest them as hardware timings.

The machine-independent part of the summary is the fused-mixtrim
structural check (acceptance): counting full-width (n, d) dot/sort
equations in the jaxpr shows the Pallas path removes the materialized
mixed stack the XLA coordinate path creates (``Y = M @ X`` + sort).

  PYTHONPATH=src python benchmarks/bench_agg_cost.py [--full]
      [--structural-only] [--json-out PATH] [--dist-out PATH]

``--dist-out`` additionally emits the per-device-count sharded-backend
comparison (``backend="pallas_sharded"`` vs ``"xla"`` wide-op counts,
fallbacks, and parity per mesh size) — run it on a forced multi-device
host (``XLA_FLAGS=--xla_force_host_platform_device_count=8``); the JSON
is the ``BENCH_dist_agg.json`` CI gate input.

``--scale-out`` emits the n-scaling hierarchical-aggregation table
(``BENCH_scale.json``): hier-vs-dense rounds/sec ratios at
n in {256, 1024, 4096, 10240} (medians of interleaved per-rep ratios —
machine-normalized, so the perf-gate floors are absolute), the
one-compile contract for the hier pipeline on both the dense-bucketing
and the ``pallas_hier`` mesh path, the zero-wide-op fact under the mesh,
mesh-vs-dense parity, and the s=1 bitwise no-op.  Also a forced
8-device-host job.  The dense n=10240 row is never EXECUTED: the XLA NNM
pipeline materializes an O(n^3) one-hot there (~4 TB) — the bench
records that infeasibility analytically and uses the dense n=256 round
as the machine-normalizing contrast for the large-n hier rows.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core import AggregatorSpec, aggregate
from repro.core import robust as robust_lib
from repro.kernels import dispatch as kdispatch
from repro.kernels.gram import gram, gram_batched
from repro.kernels.mixtrim import mixtrim
from repro.kernels.target import resolve_interpret as _interp

#: Rules the backend comparison sweeps (mda excluded from pallas timing
#: rows only because its subset enumeration dwarfs the kernel cost).
BACKEND_RULES = ("cwtm", "cwmed", "krum", "multikrum", "gm", "average")


def structural_summary(n: int = 16, d: int = 8192) -> dict:
    """Machine-independent fusion facts (see module docstring)."""
    tree = {"x": jnp.zeros((n, d), jnp.float32)}

    def wide(backend):
        spec = AggregatorSpec(rule="cwtm", f=3, pre="nnm", backend=backend)
        return kdispatch.count_wide_ops(
            lambda t: robust_lib.robust_aggregate(t, spec), tree,
            n=n, width=d)

    # A pow2-n pallas run must be fallback-free (kernels actually used).
    robust_lib.robust_aggregate(
        {"x": jnp.ones((n, d), jnp.float32)},
        AggregatorSpec(rule="cwtm", f=3, pre="nnm", backend="pallas"))
    rec = kdispatch.last_dispatch()
    pow2_fallbacks = len(rec.fallbacks)

    # Non-pow2 n=17 (the common federated case): the padded sentinel sort
    # must run the fused kernel — zero fallbacks — and match the oracle.
    rng = np.random.default_rng(17)
    t17 = {"x": jnp.asarray(rng.normal(size=(17, 777)), jnp.float32)}
    spec17 = AggregatorSpec(rule="cwtm", f=4, pre="nnm", backend="pallas")
    got17 = robust_lib.robust_aggregate(t17, spec17)
    rec17 = kdispatch.last_dispatch()
    ref17 = robust_lib.robust_aggregate(
        t17, AggregatorSpec(rule="cwtm", f=4, pre="nnm", backend="xla"))
    err17 = float(jnp.abs(got17["x"] - ref17["x"]).max())
    return {
        "kind": "agg_cost",
        "n": n,
        "d": d,
        "mixed_stack_wide_ops_xla": wide("xla"),
        "mixed_stack_wide_ops_pallas": wide("pallas"),
        "mixtrim_fallbacks_pow2": pow2_fallbacks,
        "mixtrim_fallbacks_n17": len(rec17.fallbacks),
        "padded_mixtrim_parity_ok": int(err17 < 1e-4),
        "padded_mixtrim_parity_maxerr": err17,
    }


def dist_summary(n: int = 16, d: int = 8192) -> dict:
    """Per-device-count backend comparison (machine-independent structure).

    Runs only under a multi-device host (CI forces 8 CPU devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  For each
    device count k (1-D mesh over the first k devices), counts full-width
    (n, d) dot/sort equations for ``backend="xla"`` vs
    ``backend="pallas_sharded"`` and records the sharded run's fallbacks
    and xla parity — the CI gate input for ``BENCH_dist_agg.json``.
    """
    devices = jax.devices()
    if len(devices) < 2:
        raise SystemExit(
            "bench_agg_cost --dist-out needs a multi-device host: a "
            "1-device run only produces the DEGRADED pallas_sharded row, "
            "which would trip the perf gate as a phantom regression.  "
            "Re-run with XLA_FLAGS=--xla_force_host_platform_device_count=8")
    counts = [k for k in (1, 2, 4, 8) if k <= len(devices)]
    rng = np.random.default_rng(0)
    tree = {"x": jnp.asarray(rng.normal(size=(n, d)), jnp.float32)}
    spec_s = AggregatorSpec(rule="cwtm", f=3, pre="nnm",
                            backend="pallas_sharded")
    spec_x = AggregatorSpec(rule="cwtm", f=3, pre="nnm", backend="xla")
    ref = robust_lib.robust_aggregate(tree, spec_x)

    per_dc = {}
    for k in counts:
        mesh = jax.sharding.Mesh(np.asarray(devices[:k]), ("shard",))
        with jax.set_mesh(mesh):
            wide_s = kdispatch.count_wide_ops(
                lambda t: robust_lib.robust_aggregate(t, spec_s), tree,
                n=n, width=d)
            wide_x = kdispatch.count_wide_ops(
                lambda t: robust_lib.robust_aggregate(t, spec_x), tree,
                n=n, width=d)
            got = robust_lib.robust_aggregate(tree, spec_s)
            rec = kdispatch.last_dispatch()
            err = float(jnp.abs(got["x"] - ref["x"]).max())
        row = {"wide_ops_sharded": wide_s, "wide_ops_xla": wide_x,
               "mesh_devices": rec.mesh_devices, "fallbacks":
                   len(rec.fallbacks), "parity_maxerr_vs_xla": err}
        per_dc[str(k)] = row
        emit(f"dist_agg_dc{k}_wide_ops_sharded", float(wide_s),
             f"n{n}_d{d},mesh_devices={rec.mesh_devices}")
    last = str(counts[-1])
    return {
        "kind": "dist_agg",
        "n": n,
        "d": d,
        "device_counts": counts,
        "per_device_count": per_dc,
        # flat gate keys for scripts/perf_gate.py (dc = max available)
        "sharded_wide_ops_max_dc": per_dc[last]["wide_ops_sharded"],
        "sharded_fallbacks_max_dc": per_dc[last]["fallbacks"],
        "sharded_parity_ok": int(per_dc[last]["parity_maxerr_vs_xla"]
                                 < 1e-4),
        "wide_ops_xla": per_dc[last]["wide_ops_xla"],
    }


#: n grid of the hierarchical scale-out table.  d shrinks as n grows
#: (d = 2^19 / n clamped to [64, 2048]) so the stack stays ~0.5M
#: elements: the sweep isolates the WORKER-axis scaling, which is where
#: the O(n^2)/O(n^3) dense stages live.
SCALE_NS = (256, 1024, 4096, 10240)
#: Dense rows are only executed where the XLA NNM pipeline fits in
#: host memory (its neighbor one-hot is O(n^2 * (n - f)) elements);
#: beyond this the dense contrast is the n=256 round via interleaved
#: ratios.
SCALE_DENSE_NS = (256, 1024)


def _scale_case(n: int):
    """(tree, d, f, hier spec, dense spec) for one scale row."""
    d = min(2048, max(64, (1 << 19) // n))
    f = max(1, n // 32)
    rng = np.random.default_rng(n)
    tree = {"x": jnp.asarray(rng.normal(size=(n, d)), jnp.float32)}
    hier = AggregatorSpec(rule="cwtm", f=f, pre="nnm", hier=True,
                          bucket_size=16, backend="xla")
    dense = AggregatorSpec(rule="cwtm", f=f, pre="nnm", backend="xla")
    return tree, d, f, hier, dense


def scale_summary(reps: int = 5) -> dict:
    """n-scaling hierarchical-aggregation facts (the BENCH_scale.json CI
    gate input; run under a forced 8-device host).

    Machine-normalized throughput: every gated ratio is a median of
    per-rep INTERLEAVED wall-time ratios (``timed_interleaved``), so a
    uniformly slower runner moves numerator and denominator together and
    the perf-gate floors are absolute.  The dense n=256 round is the
    shared contrast for the n=4096/10240 hier rows, whose dense
    counterparts cannot run at all (O(n^3) one-hot).  The ``pallas_hier``
    mesh path is executed once for parity/fallbacks/compile facts —
    interpret-mode off-TPU, so its wall times live under the quarantined
    ``"interpret"`` key and are never gated.
    """
    from benchmarks.common import median, timed_interleaved
    from repro.core.bucketing import num_buckets

    devices = jax.devices()
    if len(devices) < 2:
        raise SystemExit(
            "bench_agg_cost --scale-out needs a multi-device host: the "
            "pallas_hier rows on one device only produce the DEGRADED "
            "dense-bucketing path, which would trip the perf gate as a "
            "phantom regression.  Re-run with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")

    key = jax.random.PRNGKey(7)
    key2 = jax.random.PRNGKey(11)

    # --- timing sweep: one interleaved protocol across every row -------
    cases = {n: _scale_case(n) for n in SCALE_NS}
    hier_fns, dense_fns = {}, {}
    for n, (tree, d, f, spec_h, spec_d) in cases.items():
        jh = jax.jit(lambda t, k, s=spec_h:
                     robust_lib.robust_aggregate(t, s, key=k))
        hier_fns[n] = (lambda jh=jh, tree=tree:
                       jax.block_until_ready(jh(tree, key)))
        if n in SCALE_DENSE_NS:
            jd = jax.jit(lambda t, s=spec_d:
                         robust_lib.robust_aggregate(t, s))
            dense_fns[n] = (lambda jd=jd, tree=tree:
                            jax.block_until_ready(jd(tree)))
    order = [("dense", n) for n in SCALE_DENSE_NS] + \
        [("hier", n) for n in SCALE_NS]
    times = timed_interleaved(
        [dense_fns[n] if kind == "dense" else hier_fns[n]
         for kind, n in order], reps=reps)
    per_rep = {tag: slot for tag, slot in zip(order, times)}

    def ratio(num_tag, den_tag):
        return median(sorted(a / b for a, b in
                             zip(per_rep[num_tag], per_rep[den_tag])))

    per_n = {}
    for n in SCALE_NS:
        tree, d, f, spec_h, _ = cases[n]
        row = {"d": d, "f": f, "bucket_size": 16,
               "n_buckets": num_buckets(n, 16),
               "hier_round_s": median(per_rep[("hier", n)])}
        if n in SCALE_DENSE_NS:
            row["dense_round_s"] = median(per_rep[("dense", n)])
            row["hier_speedup"] = ratio(("dense", n), ("hier", n))
        row["round_ratio_vs_dense256"] = ratio(("dense", 256), ("hier", n))
        per_n[str(n)] = row
        emit(f"scale_hier_n{n}", row["hier_round_s"] * 1e6,
             f"d{d}_f{f}_s16,ratio_vs_dense256="
             f"x{row['round_ratio_vs_dense256']:.2f}")

    # --- dense n=10240 infeasibility (analytic, never executed) --------
    n_big = SCALE_NS[-1]
    f_big = cases[n_big][2]
    onehot_bytes = 4 * n_big * (n_big - f_big) * n_big
    dense_infeasible = int(onehot_bytes > 64 << 30)

    # --- compile counts: one trace across keys AND data ----------------
    tree_b, d_big, _, spec_hb, spec_db = cases[n_big]
    jh = jax.jit(lambda t, k: robust_lib.robust_aggregate(t, spec_hb,
                                                          key=k))
    tree_b2 = {"x": tree_b["x"] + 1.0}
    jax.block_until_ready(jh(tree_b, key))
    jax.block_until_ready(jh(tree_b2, key2))
    compile_count_hier = jh._cache_size()

    # --- mesh path: parity / fallbacks / wide ops / one compile --------
    spec_m = dataclasses.replace(spec_hb, backend="pallas_hier")
    jm = jax.jit(lambda t, k: robust_lib.robust_aggregate(t, spec_m,
                                                          key=k))
    got = jax.block_until_ready(jm(tree_b, key))
    rec = kdispatch.last_dispatch()
    jax.block_until_ready(jm(tree_b2, key2))
    compile_count_hier_mesh = jm._cache_size()
    ref = jh(tree_b, key)
    mesh_err = float(jnp.abs(got["x"] - ref["x"]).max())
    wide_hier = kdispatch.count_wide_ops(
        lambda t: robust_lib.robust_aggregate(t, spec_m, key=key), tree_b,
        n=n_big, width=d_big)
    # Contrast row (trace only — the dense jaxpr is abstract, no 4 TB
    # buffer): the XLA pipeline it replaces still holds wide ops.
    wide_dense = kdispatch.count_wide_ops(
        lambda t: robust_lib.robust_aggregate(t, spec_db), tree_b,
        n=n_big, width=d_big)
    emit("scale_hier_wide_ops_mesh", float(wide_hier),
         f"n{n_big}_d{d_big},mesh={rec.mesh_devices}dev")

    # --- s=1 bitwise no-op ---------------------------------------------
    tree_s, _, _, spec_h1, spec_d1 = _scale_case(SCALE_NS[0])
    spec_h1 = dataclasses.replace(spec_h1, bucket_size=1)
    got_s1 = robust_lib.robust_aggregate(tree_s, spec_h1, key=key)
    ref_s1 = robust_lib.robust_aggregate(tree_s, spec_d1)
    s1_bitwise = int(np.array_equal(np.asarray(got_s1["x"]),
                                    np.asarray(ref_s1["x"])))

    summary = {
        "kind": "scale_agg",
        "ns": list(SCALE_NS),
        "device_count": len(devices),
        "mesh_devices": rec.mesh_devices,
        "mesh_worker_axis": rec.mesh_worker_axis,
        "per_n": per_n,
        # flat gate keys for scripts/perf_gate.py --scale
        "hier_speedup_n256": per_n["256"]["hier_speedup"],
        "hier_speedup_n1024": per_n["1024"]["hier_speedup"],
        "hier_round_ratio_n4096": per_n["4096"]["round_ratio_vs_dense256"],
        "hier_round_ratio_n10240":
            per_n["10240"]["round_ratio_vs_dense256"],
        "compile_count_hier": compile_count_hier,
        "compile_count_hier_mesh": compile_count_hier_mesh,
        "hier_wide_ops_max": wide_hier,
        "hier_wide_ops_xla": wide_dense,
        "hier_fallbacks_mesh": len(rec.fallbacks),
        "hier_parity_ok": int(mesh_err < 1e-4),
        "hier_parity_maxerr": mesh_err,
        "hier_s1_bitwise_ok": s1_bitwise,
        "dense_infeasible_n10240": dense_infeasible,
        "dense_onehot_bytes_n10240": onehot_bytes,
    }
    if _interp():
        t0 = time.perf_counter()
        jax.block_until_ready(jm(tree_b, key))
        summary["interpret"] = {
            "hier_mesh_round_s": time.perf_counter() - t0}
    return summary


def bench_backends(fast: bool) -> dict:
    """backend="xla" vs backend="pallas" per rule on one dense tree."""
    n, d = 16, 8192 if fast else 65536
    rng = np.random.default_rng(0)
    tree = {"x": jnp.asarray(rng.normal(size=(n, d)), jnp.float32)}
    tag = "_interp" if _interp() else ""
    derived_tag = "interpret=1" if _interp() else "interpret=0"
    interp_rows = {}
    for rule in BACKEND_RULES:
        row = {}
        for backend in ("xla", "pallas"):
            spec = AggregatorSpec(rule=rule, f=3, pre="nnm", backend=backend)
            fn = jax.jit(lambda t, spec=spec:
                         robust_lib.robust_aggregate(t, spec))
            us = time_fn(fn, tree, iters=5)
            suffix = tag if backend == "pallas" else ""
            emit(f"agg_{rule}_nnm_{backend}{suffix}", us,
                 f"n{n}_d{d}," + (derived_tag if backend == "pallas"
                                  else "interpret=0"))
            row[backend] = us
        if _interp():
            interp_rows[f"agg_{rule}_nnm_pallas_us"] = row["pallas"]
        ratio = row["xla"] / row["pallas"] if row["pallas"] else float("nan")
        emit(f"agg_{rule}_nnm_backend_ratio{tag}", 0.0,
             f"xla_over_pallas=x{ratio:.2f},{derived_tag}")
    return interp_rows


def bench_kernels(fast: bool) -> dict:
    """Primitive kernel rows (interpret mode off-TPU — tagged)."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (16, 8192))
    xb = jax.random.normal(key, (8, 16, 8192))
    m = jnp.eye(16) * 0.5 + jnp.ones((16, 16)) / 32
    tag = "_interp" if _interp() else ""
    derived = "interpret=1" if _interp() else "interpret=0"
    rows = {
        f"kernel_gram{tag}": time_fn(lambda: gram(x), iters=3),
        f"kernel_gram_batched_B8{tag}":
            time_fn(lambda: gram_batched(xb), iters=3),
        f"kernel_mixtrim{tag}":
            time_fn(lambda: mixtrim(x, m, f=3, mode="trim"), iters=3),
    }
    for name, us in rows.items():
        emit(name, us, f"n16_d8192,{derived}")
    return {f"{k}_us": v for k, v in rows.items()} if _interp() else {}


def main(fast: bool = True, *, json_out: str | None = None,
         structural_only: bool = False,
         dist_out: str | None = None,
         scale_out: str | None = None) -> dict:
    summary = structural_summary()
    emit("mixed_stack_wide_ops_xla",
         float(summary["mixed_stack_wide_ops_xla"]), "jaxpr_dot+sort_n_d")
    emit("mixed_stack_wide_ops_pallas",
         float(summary["mixed_stack_wide_ops_pallas"]), "jaxpr_dot+sort_n_d")
    emit("mixtrim_fallbacks_n17",
         float(summary["mixtrim_fallbacks_n17"]), "padded_sentinel_sort")

    if dist_out:
        dist = dist_summary()
        with open(dist_out, "w") as fh:
            json.dump(dist, fh, indent=2, sort_keys=True)
        print(f"wrote {dist_out}")

    if scale_out:
        scale = scale_summary()
        with open(scale_out, "w") as fh:
            json.dump(scale, fh, indent=2, sort_keys=True)
        print(f"wrote {scale_out}")

    interp_rows: dict = {}
    if not structural_only:
        ns = (16, 32) if fast else (16, 32, 64)
        ds = (1024, 8192) if fast else (1024, 8192, 65536)
        rules = ("cwtm", "gm", "krum", "cwmed", "mda", "meamed", "multikrum")
        key = jax.random.PRNGKey(0)
        for rule in rules:
            for pre in (None, "nnm"):
                times = {}
                for n in ns:
                    for d in ds:
                        x = jax.random.normal(key, (n, d))
                        spec = AggregatorSpec(rule=rule, f=n // 4, pre=pre)
                        fn = jax.jit(lambda s, spec=spec: aggregate(s, spec))
                        times[(n, d)] = time_fn(fn, x, iters=5)
                n0 = ns[0]
                expo = np.log(times[(n0, ds[-1])] / times[(n0, ds[0])]) / \
                    np.log(ds[-1] / ds[0])
                emit(f"cost_{rule}_{pre or 'vanilla'}",
                     times[(ns[-1], ds[-1])], f"d_scaling_exp={expo:.2f}")
        interp_rows.update(bench_backends(fast))
        interp_rows.update(bench_kernels(fast))

    if interp_rows:
        summary["interpret"] = interp_rows
    if json_out:
        with open(json_out, "w") as fh:
            json.dump(summary, fh, indent=2, sort_keys=True)
        print(f"wrote {json_out}")
    return summary


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--structural-only", action="store_true",
                    help="skip timing sweeps; emit only the machine-"
                         "independent fusion facts (CI gate input)")
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--dist-out", default=None,
                    help="also emit the per-device-count sharded-backend "
                         "comparison (run under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--scale-out", default=None,
                    help="also emit the n-scaling hierarchical-"
                         "aggregation table (BENCH_scale.json; run under "
                         "XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    args = ap.parse_args()
    main(fast=not args.full, json_out=args.json_out,
         structural_only=args.structural_only, dist_out=args.dist_out,
         scale_out=args.scale_out)
