"""End-to-end training driver.

CPU-scale (default): trains a reduced variant of any assigned arch with the
full robust pipeline (Dirichlet-heterogeneous synthetic LM data, D-SHB +
NNM+agg, Byzantine attack simulation, checkpointing, kappa-hat tracking).
``--full`` runs the published widths and needs a TPU: the whole model, or,
for an arch whose config module states one chip's share of a deployment
(``repro.configs.chip_config``), that share.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --steps 200 --workers 8 --byz 2 --attack alie --agg nnm+cwtm
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --full \
      --workers 4 --byz 1 --steps 3     # published widths, one TPU chip
  PYTHONPATH=src python -m repro.launch.train --arch mellum2-12b-a2.5b \
      --full --workers 4 --byz 1 --batch 1 --seq 512 --steps 3
                                        # one chip's share of 8-way EP
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Iterator, Optional, Sequence

import jax
import numpy as np

from repro import obs
from repro.checkpoint import save_checkpoint
from repro.configs import ARCH_IDS, chip_config, reduced_config
from repro.configs.base import ModelConfig
from repro.core.types import AggregatorSpec
from repro.data import build_heterogeneous, make_lm_corpus, worker_batches
from repro.kernels.target import on_tpu
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim import sgd
from repro.optim.schedules import cosine
from repro.training import ByzantineConfig, TrainerConfig, build_train_step, init_state


def parse_agg(s: str) -> AggregatorSpec:
    pre, _, rule = s.rpartition("+")
    return AggregatorSpec(rule=rule or "cwtm", pre=pre or None)


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=ARCH_IDS)
    ap.add_argument("--full", action="store_true",
                    help="use the full-scale config, or one chip's share of "
                         "it where the arch states one (needs a TPU)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--byz", type=int, default=2)
    ap.add_argument("--attack", default="alie")
    ap.add_argument("--agg", default="nnm+cwtm")
    ap.add_argument("--algorithm", default="dshb", choices=["dshb", "dgd"])
    ap.add_argument("--beta", type=float, default=0.9)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--batch", type=int, default=4, help="per-worker batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--alpha", type=float, default=0.1,
                    help="Dirichlet heterogeneity")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


@dataclasses.dataclass
class Run:
    """Everything one training run is built from, before any step."""
    args: argparse.Namespace
    cfg: ModelConfig
    model: Any
    tcfg: TrainerConfig
    optimizer: Any
    schedule: Any
    batches: Iterator[dict]

    def init_state(self) -> dict:
        """Step-0 train state: params from ``--seed``, zero momenta."""
        params = self.model.init(jax.random.PRNGKey(self.args.seed))
        return init_state(params, self.optimizer, self.args.workers,
                          self.tcfg)

    def step_fn(self):
        """The jitted train step; it donates the state it is given."""
        return jax.jit(build_train_step(self.model.loss, self.optimizer,
                                        self.tcfg, self.schedule),
                       donate_argnums=0)


def setup(args: argparse.Namespace, *, backend: str = "auto") -> Run:
    """Model, trainer config and data stream of a run (no device work
    beyond what the data pipeline needs).  ``backend`` is the aggregation
    kernel backend (see ``repro.kernels.dispatch``)."""
    if args.full and not on_tpu():
        raise SystemExit(
            f"--full runs {args.arch} at its published widths: a TPU is "
            f"required (found {jax.default_backend()}); drop --full for the "
            f"reduced CPU config")
    cfg = chip_config(args.arch) if args.full else reduced_config(args.arch)

    # Heterogeneous LM data: Dirichlet over topics.
    seqs, topics = make_lm_corpus(n_tokens=400_000, vocab=cfg.vocab_size,
                                  seq_len=args.seq + 1, seed=args.seed)
    ds = build_heterogeneous({"seq": seqs, "y": topics}, "y", args.workers,
                             alpha=args.alpha, seed=args.seed)
    raw = worker_batches(ds, args.batch, seed=args.seed)

    def batches():
        for b in raw:
            seq = b["seq"]
            batch = {"tokens": seq[..., :-1], "labels": seq[..., 1:]}
            if cfg.family == "vlm":
                w, pb = seq.shape[:2]
                batch["patches"] = np.zeros(
                    (w, pb, cfg.num_patches, cfg.vision_dim), np.float32)
                batch["tokens"] = batch["tokens"][..., :args.seq - cfg.num_patches]
                batch["labels"] = batch["labels"][..., :args.seq - cfg.num_patches]
            if cfg.family == "encdec":
                w, pb = seq.shape[:2]
                batch["frames"] = np.zeros(
                    (w, pb, cfg.encoder_seq, cfg.d_model), np.float32)
            yield batch

    agg = parse_agg(args.agg)
    tcfg = TrainerConfig(
        algorithm=args.algorithm, beta=args.beta,
        agg=dataclasses.replace(agg, f=args.byz, backend=backend),
        byz=ByzantineConfig(f=args.byz, attack=args.attack),
    )
    return Run(args=args, cfg=cfg, model=build_model(cfg), tcfg=tcfg,
               optimizer=sgd(clip=2.0),
               schedule=cosine(args.lr, args.steps,
                               warmup=min(20, args.steps // 10)),
               batches=batches())


def count_routing(metrics: dict) -> None:
    """Add one step's routing counts (expert layers only) to the
    ``obs.runtime`` counters: ``moe.routed_pairs`` sums the (token, held
    expert) pairs over steps; ``moe.expert_load_max`` / ``_min`` follow
    the largest and smallest load one held expert took in any step."""
    if "routed_pairs" not in metrics:
        return
    obs.inc("moe.routed_pairs", float(metrics["routed_pairs"]))
    seen = obs.counters()
    for name, pick in (("expert_load_max", max), ("expert_load_min", min)):
        key, value = f"moe.{name}", float(metrics[name])
        if key in seen:
            value = pick(value, seen[key])
        obs.inc(key, value - seen.get(key, 0.0))


def main(argv: Optional[Sequence[str]] = None) -> dict:
    """Train; returns the run's record: config, parameter count, compile
    seconds, the compiled step's memory analysis, per-step metrics and
    seconds, and the final state."""
    args = parse_args(argv)
    run = setup(args)
    enable_compile_cache()
    state = run.init_state()
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state["params"]))
    print(f"arch={run.cfg.name} params={n_params/1e6:.2f}M "
          f"workers={args.workers} f={args.byz} attack={args.attack} "
          f"agg={args.agg}")

    key = jax.random.PRNGKey(args.seed)
    key, sub = jax.random.split(key)
    batch = next(run.batches)
    t0 = time.perf_counter()
    step_fn = run.step_fn().lower(state, batch, sub).compile()
    compile_s = time.perf_counter() - t0

    history = []
    for t in range(args.steps):
        if t:
            key, sub = jax.random.split(key)
            batch = next(run.batches)
        t0 = time.perf_counter()
        state, metrics = step_fn(state, batch, sub)
        metrics = jax.device_get(jax.block_until_ready((state, metrics))[1])
        count_routing(metrics)
        row = {"step": t + 1, "seconds": time.perf_counter() - t0,
               **{k: float(v) for k, v in metrics.items()
                  if np.ndim(v) == 0}}
        history.append(row)
        if (t + 1) % args.log_every == 0 or t == 0:
            print(f"step {t+1:5d} loss={row['loss']:.4f} "
                  f"|R|={row['direction_norm']:.3f} "
                  f"kappa_hat={row.get('kappa_hat', 0):.3f} "
                  f"lr={row['lr']:.4f} ({row['seconds']:.2f}s/step)")

    if args.checkpoint:
        save_checkpoint(args.checkpoint, state["params"],
                        step=int(state["step"]))
        print(f"checkpoint saved to {args.checkpoint}")
    return {"config": run.cfg, "n_params": n_params,
            "compile_seconds": compile_s,
            "memory": step_fn.memory_analysis(), "history": history,
            "state": state}


if __name__ == "__main__":
    main()
