"""Smoke run of the robust train step on a TPU: the quickest proof that the
system still starts on the chip.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # four chips: sharded aggregation only

One chip drives ``repro.launch.train`` through its ``main`` at
SmolLM-360M's published widths (n=4 workers, f=1, D-SHB, NNM+CWTM, ALIE,
kernel backend "auto"): three steps with finite loss and kappa-hat, the
kernel dispatch record (Pallas, compiled, no fallback), the fused mix+trim
kernel against a float64 host oracle, and one step of the kernel path
against the same step on the "xla" backend.

``--chips 4`` runs only the multi-chip aggregation: ``robust_aggregate``
with "pallas_sharded" (n=17, f=4, NNM+CWTM) and "pallas_hier" (n=1024 on
the 2x2 workers x model mesh) on stacks of a few GB placed across the
four chips, each against "xla" on the same stack.

Every phase must pass; any failure exits non-zero before the last line.
Off-TPU the script stops at once.  The last line of standard output is
one JSON object: {"ok": true, "device": {platform, kind, count}}.  The
times printed are smoke timings of a cold process, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: The one-chip run: the train driver's own arguments.
TRAIN_ARGV = ["--arch", "smollm-360m", "--full", "--workers", "4",
              "--byz", "1", "--agg", "nnm+cwtm", "--attack", "alie",
              "--steps", "3", "--log-every", "1"]

#: Kernel path vs "xla" on one train step from the same state, batch and
#: key.  "params": the largest difference of the updated parameters over
#: the largest |parameter| of the leaf.  They are stored in bf16, so where
#: the two fp32 update directions straddle a rounding boundary the copies
#: differ by one bf16 rounding, at most 2^-7 of a value; one step's update
#: itself is far smaller than that.  "metrics": the relative difference of
#: the step's fp32 direction norm and kappa-hat, computed from the
#: aggregate before it is cast — the check that sees a wrong aggregate.  A
#: TPU fp32 dot at default precision rounds its operands to bf16 (2^-8
#: relative), as the xla side's gram and NNM mix do; 2e-2 is 5 x 2^-8,
#: where a wrong sort or neighbour set errs by order 1.
PARITY_TOL = {"params": 2.0**-7, "metrics": 2e-2}

#: Aggregates of a random stack (kernel check, four-chip phase) against a
#: reference, as the largest difference over the stack's largest |value|:
#: the scale at which operand rounding acts.  With no mix the kernel only
#: sorts and sums in fp32 (~1e-7); a mix or bucket dot at default TPU
#: precision may add 2^-8.
AGG_TOL = {"fp32": 1e-5, "dot": 1e-2}

#: The kernel check: the paper's n=17, f=4 (the sentinel-padded sort).
KERNEL_CHECK = {"n": 17, "f": 4, "d": 2**20}

#: Four-chip stacks: (n, D) fp32, a few GB each, split over the chips.
SHARDED_SHAPE = (17, 48 * 2**20)        # 3.4 GB
HIER_SHAPE = (1024, 3 * 2**18)          # 3.2 GB


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def tpu_devices(count: int):
    """The chips, or a failure when JAX finds no TPU or too few."""
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}")
    check(len(devs) >= count, f"{count} chips needed, {len(devs)} found")
    return devs[:count]


def rel_err(got, want, scale=None) -> float:
    """max |got - want| over matching pytrees (host float64), divided by
    ``scale`` or else by max |want|."""
    import jax
    import numpy as np
    num = den = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        num = max(num, float(np.abs(a - b).max()))
        den = max(den, float(np.abs(b).max()))
    return num / (den if scale is None else scale)


def check_dispatch(expect_backend: str) -> None:
    """The head dispatch record ran ``expect_backend``'s compiled kernels."""
    from repro.kernels.dispatch import last_dispatch
    rec = last_dispatch()
    check(rec is not None, "no kernel dispatch was recorded")
    print(f"dispatch: {rec.describe()}")
    check(rec.backend == expect_backend,
          f"backend {rec.backend!r}, expected {expect_backend!r}")
    check(not any("-interpret" in d.used for d in rec.decisions),
          "a kernel ran in interpret mode")
    check(not rec.fallbacks, f"kernel fallbacks: {rec.fallbacks}")


def peak_gb(dev) -> float:
    return dev.memory_stats()["peak_bytes_in_use"] / 1e9


def train_phase(dev) -> None:
    """Three train steps through the driver's main at published widths."""
    import numpy as np
    from repro.launch import train

    out = train.main(TRAIN_ARGV)
    cfg, hist = out["config"], out["history"]
    args = train.parse_args(TRAIN_ARGV)
    print(f"model {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads} kv_heads={cfg.num_kv_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"tied={cfg.tie_embeddings} params={out['n_params']} "
          f"n={args.workers} f={args.byz}")
    for row in hist:
        print(f"step {row['step']}: loss={row['loss']!r} "
              f"kappa_hat={row['kappa_hat']!r} seconds={row['seconds']!r}")
        check(np.isfinite(row["loss"]) and np.isfinite(row["kappa_hat"]),
              f"step {row['step']}: non-finite loss or kappa_hat")
    check(len(hist) == args.steps, f"{len(hist)} of {args.steps} steps ran")
    check_dispatch("pallas")
    steady = float(np.median([r["seconds"] for r in hist[1:]]))
    print(f"smoke timings (cold process, not a benchmark): compile "
          f"{out['compile_seconds']!r} s, steady step {steady!r} s")
    mem = out["memory"]
    print(f"compiled step: arguments {mem.argument_size_in_bytes / 1e9!r} "
          f"GB, temporaries {mem.temp_size_in_bytes / 1e9!r} GB, code "
          f"{mem.generated_code_size_in_bytes / 1e9!r} GB")
    print(f"peak_bytes_in_use after training: {peak_gb(dev)!r} GB "
          f"(live arrays and code; the step's temporaries are not in it); "
          f"memory_stats {dev.memory_stats()!r}")


def kernel_phase(key) -> None:
    """The fused mix+trim kernel on the chip (the paper's n=17, f=4: the
    sentinel-padded sort) and its jnp form on XLA, against a float64 host
    oracle.  The two mixed rows show each side's fp32 dot precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.mixtrim import mixtrim, mixtrim_ref

    n, f, d = KERNEL_CHECK["n"], KERNEL_CHECK["f"], KERNEL_CHECK["d"]
    kx, km = jax.random.split(key)
    x = jax.random.normal(kx, (n, d), jnp.float32)
    m = jax.nn.softmax(jax.random.normal(km, (n, n)), axis=-1)
    x64, m64 = np.asarray(x, np.float64), np.asarray(m, np.float64)
    scale = float(np.abs(x64).max())
    xla_trim = jax.jit(mixtrim_ref, static_argnums=2)
    for name, got, y64, tol in (
            ("pallas, no mix", mixtrim(x, None, f=f), x64, AGG_TOL["fp32"]),
            ("pallas, mix", mixtrim(x, m, f=f), m64 @ x64, AGG_TOL["dot"]),
            ("xla, mix", xla_trim(x, m, f), m64 @ x64, AGG_TOL["dot"])):
        want = np.sort(y64, axis=0)[f:n - f].mean(axis=0)
        err = rel_err(got, want, scale)
        print(f"mixtrim n={n} f={f} d={d} {name}: err vs float64 / max|x| "
              f"{err!r} (tol {tol})")
        check(err <= tol, f"mixtrim {name}: {err} > {tol}")


def parity_phase(dev) -> None:
    """One step from the same state, batch and key on the kernel path
    ("auto" -> "pallas") and on "xla"."""
    import jax
    import numpy as np
    from repro.launch import train

    args = train.parse_args(TRAIN_ARGV)
    key = jax.random.split(jax.random.PRNGKey(args.seed))[1]
    prec = jax.config.jax_default_matmul_precision or "default"
    print(f"parity: fp32 dots at jax_default_matmul_precision={prec} on both "
          f"sides; the mixtrim rows above show what that means on each side "
          f"(err ~1e-7: fp32 passes, ~1e-3: one bf16 pass)")
    params, metrics = {}, {}
    for backend in ("auto", "xla"):
        run = train.setup(args, backend=backend)
        new, m = run.step_fn()(run.init_state(), next(run.batches), key)
        params[backend] = jax.device_get(new["params"])
        metrics[backend] = {k: float(v) for k, v in m.items()
                            if np.ndim(v) == 0}
        print(f"parity {backend}: {metrics[backend]!r}")
        check_dispatch("pallas" if backend == "auto" else "xla")
        del new
    par_err = max(rel_err(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(params["auto"]),
        jax.tree_util.tree_leaves(params["xla"])))
    met_err = max(abs(metrics["auto"][k] - metrics["xla"][k])
                  / abs(metrics["xla"][k])
                  for k in ("direction_norm", "kappa_hat"))
    print(f"parity pallas vs xla: updated params max rel diff {par_err!r} "
          f"(tol {PARITY_TOL['params']!r}); direction_norm / kappa_hat rel "
          f"diff {met_err!r} (tol {PARITY_TOL['metrics']!r})")
    check(par_err <= PARITY_TOL["params"],
          f"parity params {par_err} > {PARITY_TOL['params']}")
    check(met_err <= PARITY_TOL["metrics"],
          f"parity metrics {met_err} > {PARITY_TOL['metrics']}")
    print(f"peak_bytes_in_use after parity: {peak_gb(dev)!r} GB")


def four_chip_phase(devs) -> None:
    """Sharded and hierarchical aggregation across four chips vs xla."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import AggregatorSpec
    from repro.core.robust import robust_aggregate
    from repro.launch.mesh import make_hier_mesh

    def place(shape, mesh, spec, seed):
        sharding = NamedSharding(mesh, spec)
        make = jax.jit(lambda k: jax.random.normal(k, shape),
                       out_shardings=sharding)
        x = make(jax.random.PRNGKey(seed))
        per_dev = [s.data.nbytes / 1e9 for s in x.addressable_shards]
        print(f"stack {shape} = {x.nbytes / 1e9!r} GB over "
              f"{len(per_dev)} chips, {per_dev[0]!r} GB each")
        check(len({s.device for s in x.addressable_shards}) == len(devs),
              "stack is not spread over every chip")
        return x

    def compare(tag, x, spec, key=None):
        agg = jax.jit(lambda t: robust_aggregate(t, spec, key=key))
        t0 = time.perf_counter()
        got = jax.block_until_ready(agg({"x": x}))["x"]
        print(f"{tag}: smoke time (with compile) "
              f"{time.perf_counter() - t0!r} s")
        check_dispatch(spec.backend)
        print(f"{tag}: peak_bytes_in_use per chip "
              f"{[peak_gb(d) for d in devs]!r} GB")
        # The same stages on "xla" (a hier spec keeps its bucketing stage).
        ref_spec = dataclasses.replace(spec, backend="xla",
                                       hier=spec.backend == "pallas_hier")
        ref = jax.jit(lambda t: robust_aggregate(t, ref_spec, key=key))(
            {"x": x})["x"]
        scale = float(jnp.abs(x).max())
        err = rel_err(np.asarray(got), np.asarray(ref), scale)
        print(f"{tag} vs xla: err / max|x| {err!r} (tol {AGG_TOL['dot']})")
        check(err <= AGG_TOL["dot"], f"{tag}: {err} > {AGG_TOL['dot']}")

    mesh = jax.sharding.Mesh(np.asarray(devs), ("shard",))
    x = place(SHARDED_SHAPE, mesh, P(None, "shard"), 0)
    with jax.set_mesh(mesh):
        compare("pallas_sharded n=17 f=4", x,
                AggregatorSpec(rule="cwtm", f=4, pre="nnm",
                               backend="pallas_sharded"))
    del x

    mesh = make_hier_mesh(2, 2)
    x = place(HIER_SHAPE, mesh, P("workers", "model"), 1)
    with jax.set_mesh(mesh):
        compare("pallas_hier n=1024 f=16 s=8", x,
                AggregatorSpec(rule="cwtm", f=16, pre="nnm", bucket_size=8,
                               backend="pallas_hier"),
                key=jax.random.PRNGKey(2))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip aggregation phase")
    args = ap.parse_args(argv)

    devs = tpu_devices(args.chips)
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    print(f"jax {jax.__version__} on {devs[0].device_kind} x {len(devs)}; "
          f"compile cache {enable_compile_cache()}")
    if args.chips == 4:
        four_chip_phase(devs)
    else:
        train_phase(devs[0])
        kernel_phase(jax.random.PRNGKey(7))
        parity_phase(devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
