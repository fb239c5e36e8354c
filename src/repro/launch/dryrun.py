import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede every other import (jax locks device count on first init).
# This file is the ONLY place the 512-device override is set; smoke tests
# and benchmarks see the single real CPU device.

# Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
# against the production mesh with ShapeDtypeStruct stand-ins (no
# allocation), then extract memory / cost / collective analyses for the
# roofline.
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m \
#       --shape train_4k [--multi-pod] [--agg nnm+cwtm] [--out artifacts/]
#   PYTHONPATH=src python -m repro.launch.dryrun --all

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES
from repro.core.types import AggregatorSpec
from repro.launch import launch_config as lc
from repro.launch import mesh as meshlib
from repro.launch import roofline as rl
from repro.launch import specs as specslib
from repro.models import abstract, build_model, mesh_axes_scope, partition_specs
from repro.optim import sgd
from repro.optim.schedules import constant
from repro.training import TrainerConfig, build_train_step
from repro.training.trainer import split_params


def parse_agg(s: str, transport: str | None = None,
              sketch: int = 0) -> AggregatorSpec:
    pre, _, rule = s.rpartition("+")
    return AggregatorSpec(rule=rule or "cwtm", pre=pre or None,
                          transport_dtype=transport, sketch_dim=sketch)


def build_train_target(model, cfg, axes, shape, n_workers, agg: AggregatorSpec,
                       fsdp_keys, kappa_hat: bool = True):
    tcfg = TrainerConfig(
        algorithm="dgd" if fsdp_keys else "dshb",
        agg=agg, worker_axes=axes.data, fsdp_keys=fsdp_keys,
        track_kappa_hat=kappa_hat,
    )
    # AggregatorSpec.f: tolerated Byzantine count on this mesh (f < n/2).
    import dataclasses as dc
    tcfg = dc.replace(tcfg, agg=dc.replace(agg, f=max(1, n_workers // 4)),
                      byz=dc.replace(tcfg.byz, f=max(1, n_workers // 4),
                                     attack="none"))

    optimizer = sgd(clip=2.0)
    step = build_train_step(model.loss, optimizer, tcfg, constant(1e-3))

    descs = model.param_descs()
    params_abs = abstract(descs)
    params_specs = partition_specs(descs)

    state_abs = dict(params=params_abs, opt_state=(),
                     step=jax.ShapeDtypeStruct((), jnp.int32))
    state_specs = dict(params=params_specs, opt_state=(), step=P())
    if tcfg.algorithm == "dshb":
        robust_abs, _ = split_params(params_abs, fsdp_keys)
        robust_specs, _ = split_params(params_specs, fsdp_keys)
        state_abs["momentum"] = [
            jax.ShapeDtypeStruct((n_workers,) + a.shape, jnp.float32)
            for a in robust_abs]
        state_specs["momentum"] = [
            P(axes.data, *(s if isinstance(s, tuple) else tuple(s)))
            for s in robust_specs]

    batch_abs, batch_specs = specslib.train_input_specs(cfg, shape, axes,
                                                        n_workers)
    key_abs = jax.ShapeDtypeStruct((2,), jnp.uint32)
    jitted = jax.jit(step, in_shardings=meshlib.as_shardings(
        (state_specs, batch_specs, P())))
    return jitted, (state_abs, batch_abs, key_abs)


def build_prefill_target(model, cfg, axes, shape):
    descs = model.param_descs()
    params_abs, params_specs = abstract(descs), partition_specs(descs)
    batch_abs, batch_specs = specslib.prefill_input_specs(cfg, shape, axes)
    jitted = jax.jit(model.forward, in_shardings=meshlib.as_shardings(
        (params_specs, batch_specs)))
    return jitted, (params_abs, batch_abs)


def build_decode_target(model, cfg, axes, shape):
    descs = model.param_descs()
    params_abs, params_specs = abstract(descs), partition_specs(descs)
    cache_descs = model.cache_descs(shape.global_batch, shape.seq_len)
    cache_abs, cache_specs = abstract(cache_descs), partition_specs(cache_descs)
    io_abs, io_specs = specslib.decode_input_specs(cfg, shape, axes)
    jitted = jax.jit(model.decode_step,
                     in_shardings=meshlib.as_shardings(
                         (params_specs, cache_specs,
                          io_specs["tokens"], io_specs["pos"])))
    return jitted, (params_abs, cache_abs, io_abs["tokens"], io_abs["pos"])


# --------------------------------------------------------------------------
# Cost probes: XLA cost_analysis counts a while-loop body ONCE, so the full
# scan-over-layers compile under-reports flops/bytes by ~num_layers.  We
# compile two SHALLOW, FULLY-UNROLLED variants of the same target and
# extrapolate per-layer cost linearly to the full depth (embedding / head /
# aggregation fixed-cost parts are captured by the intercept).  Validated
# against analytic 6*N*D in EXPERIMENTS.md.
# --------------------------------------------------------------------------

def _probe_depths(cfg) -> tuple[tuple[int, int], int]:
    """((probe_a, probe_b) unit counts, full unit count)."""
    if cfg.family == "hybrid":
        return (1, 2), cfg.num_layers // cfg.attn_every   # units = groups
    return (2, 4), cfg.num_layers                          # units = layers


def _probe_cfg(cfg, units: int):
    kw = dict(scan_unroll=64)
    if cfg.family == "hybrid":
        kw["num_layers"] = units * cfg.attn_every
    else:
        kw["num_layers"] = units
    if cfg.family == "encdec":
        kw["encoder_layers"] = units
    return cfg.replace(**kw)


def _compile_cost(cfg, axes, shape, n_workers, agg, fsdp_keys,
                  kappa_hat=True):
    model = build_model(cfg)
    if shape.kind == "train":
        jitted, args = build_train_target(model, cfg, axes, shape, n_workers,
                                          agg, fsdp_keys,
                                          kappa_hat=kappa_hat)
    elif shape.kind == "prefill":
        jitted, args = build_prefill_target(model, cfg, axes, shape)
    else:
        jitted, args = build_decode_target(model, cfg, axes, shape)
    compiled = jitted.lower(*args).compile()
    cost = compiled.cost_analysis() or {}
    coll = rl.collective_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            float(sum(coll.values())))


def _extrapolated_cost(cfg, axes, shape, n_workers, agg, fsdp_keys,
                       kappa_hat=True):
    (ua, ub), full_units = _probe_depths(cfg)
    ca = _compile_cost(_probe_cfg(cfg, ua), axes, shape, n_workers, agg,
                       fsdp_keys, kappa_hat=kappa_hat)
    cb = _compile_cost(_probe_cfg(cfg, ub), axes, shape, n_workers, agg,
                       fsdp_keys, kappa_hat=kappa_hat)
    out = []
    for a, b in zip(ca, cb):
        slope = (b - a) / (ub - ua)
        out.append(max(a + (full_units - ua) * slope, 0.0))
    return tuple(out)   # per-device flops, hbm bytes, collective bytes


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               agg: str = "nnm+cwtm", seq_par: bool | None = None,
               cost_probe: bool = True, verbose: bool = True,
               transport: str | None = None, sketch: int = 0,
               pad_kv: bool = False, gqa_einsum: bool = False,
               kappa_hat: bool = True, capacity: float | None = None,
               variant: str = "baseline") -> dict:
    reason = lc.skip_reason(arch, shape_name)
    if reason:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": reason}

    cfg = lc.launch_config(arch, shape_name)
    if gqa_einsum:
        cfg = cfg.replace(gqa_einsum=True)
    if capacity is not None:
        cfg = cfg.replace(capacity_factor=capacity)
    if seq_par is None:
        # §Perf finding: sequence-parallel residual stream helps only the
        # FSDP giants (saved-activation pressure); it costs ~+10% memory
        # term on <=8B dense at train_4k.
        seq_par = lc.wants_fsdp_experts(cfg)
    shape = SHAPES[shape_name]
    n_workers = meshlib.n_workers(multi_pod=multi_pod)
    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    axes = meshlib.mesh_axes_for(cfg, multi_pod=multi_pod, pad_kv=pad_kv)
    if shape.kind == "train":
        import dataclasses as dc
        # Worker axis is carried by vmap(spmd_axis_name): activation specs
        # must not mention the data axes during the train trace.
        axes = dc.replace(axes, workers_on_data=True, seq_par=seq_par)
    if lc.wants_fsdp_experts(cfg):
        import dataclasses as dc
        axes = dc.replace(axes, expert_fsdp=True)

    record = {"arch": arch, "shape": shape_name,
              "mesh": "2x16x16" if multi_pod else "16x16",
              "kind": shape.kind, "agg": agg, "n_workers": n_workers,
              "variant": variant,
              "options": {"transport": transport, "sketch": sketch,
                          "pad_kv": pad_kv, "seq_par": seq_par,
                          "gqa_einsum": gqa_einsum}}
    t0 = time.time()
    with jax.set_mesh(mesh), mesh_axes_scope(axes):
        model = build_model(cfg)
        if shape.kind == "train":
            jitted, args = build_train_target(
                model, cfg, axes, shape, n_workers,
                parse_agg(agg, transport, sketch), lc.fsdp_keys_for(cfg),
                kappa_hat=kappa_hat)
        elif shape.kind == "prefill":
            jitted, args = build_prefill_target(model, cfg, axes, shape)
        else:
            jitted, args = build_decode_target(model, cfg, axes, shape)

        lowered = jitted.lower(*args)
        record["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 1)

        mem = compiled.memory_analysis()
        try:
            record["memory"] = {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0) or
                                  (mem.argument_size_in_bytes +
                                   mem.temp_size_in_bytes)),
            }
        except Exception:
            record["memory"] = {"raw": str(mem)}

        cost = compiled.cost_analysis() or {}
        record["cost"] = {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float)) and
                          k in ("flops", "bytes accessed", "transcendentals")}

        text = compiled.as_text()
        coll = rl.collective_bytes(text)
        record["collectives"] = coll

        chips = 512 if multi_pod else 256
        flops = record["cost"].get("flops", 0.0)
        hbm = record["cost"].get("bytes accessed", 0.0)
        cbytes = float(sum(coll.values()))
        record["cost_scan_raw"] = {"flops": flops, "hbm": hbm,
                                   "collective": cbytes}
        if cost_probe and not multi_pod:   # roofline table is single-pod
            t2 = time.time()
            flops, hbm, cbytes = _extrapolated_cost(
                cfg, axes, shape, n_workers,
                parse_agg(agg, transport, sketch), lc.fsdp_keys_for(cfg),
                kappa_hat=kappa_hat)
            record["probe_s"] = round(time.time() - t2, 1)
        terms = rl.RooflineTerms(flops, hbm, cbytes, meshlib.PEAK_FLOPS,
                                 meshlib.HBM_BW, meshlib.ICI_BW)
        record["roofline"] = terms.as_dict()
        tokens = (shape.global_batch * shape.seq_len if shape.kind != "decode"
                  else shape.global_batch)
        mf = rl.model_flops(cfg, tokens)
        # model_flops = 6*N*D counts fwd+bwd; inference is forward-only.
        mult = 1.0 if shape.kind == "train" else (1.0 / 3.0)
        record["model_flops_global"] = mf * mult
        record["model_flops_per_device"] = mf * mult / chips
        record["useful_flops_ratio"] = (
            record["model_flops_per_device"] / flops if flops else None)
        record["status"] = "ok"

    if verbose:
        r = record["roofline"]
        print(f"{arch:16s} {shape_name:12s} {record['mesh']:8s} "
              f"compile={record['compile_s']:6.1f}s "
              f"compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
              f"coll={r['collective_s']:.3e}s dom={r['dominant']}")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS) + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--agg", default="nnm+cwtm")
    ap.add_argument("--no-seq-par", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape_name}_{'mp' if mp else 'sp'}"
                path = os.path.join(args.out, tag + ".json")
                try:
                    rec = dryrun_one(arch, shape_name, multi_pod=mp,
                                     agg=args.agg,
                                     seq_par=not args.no_seq_par)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                    print(f"{arch} {shape_name} FAILED: {rec['error'][:200]}")
                with open(path, "w") as fh:
                    json.dump(rec, fh, indent=1)


if __name__ == "__main__":
    main()
