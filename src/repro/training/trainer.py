"""Robust distributed training: the paper's Alg. 1 (D-GD) and Alg. 3 (D-SHB)
as first-class train steps over arbitrary models.

Structure of one step (DESIGN.md §3):

  1. per-worker gradients — ``vmap(grad(loss), spmd_axis_name=worker_axes)``
     over a batch with a leading worker dim; NO cross-worker psum.
  2. worker-side momentum (D-SHB): m_i <- beta m_i + (1-beta) g_i, one
     momentum pytree per worker (worker axis sharded over the mesh, so
     per-device memory equals a single momentum).
  3. Byzantine injection (simulation/testing only): the last f worker rows
     are overwritten by the configured attack.
  4. robust aggregation over the worker axis (gram path or coordinate path)
     -> direction R_t, plus the kappa-hat diagnostic of paper Eq. (26).
  5. server optimizer applies R_t.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import robust as robust_lib
from repro.core.attacks import apply_attack_tree
from repro.core.theory import tree_kappa_hat
from repro.core.types import AggregatorSpec
from repro.optim import Optimizer, global_norm
from repro.rounds.options import RoundOptions, resolve_options

PyTree = Any
Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ByzantineConfig:
    """Simulation of f Byzantine workers executing ``attack``."""
    f: int = 0
    attack: str = "none"           # none|alie|foe|sf|lf|mimic|alie_opt|foe_opt
    eta: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    algorithm: str = "dshb"        # dgd (full grads, no momentum) | dshb
    beta: float = 0.9              # momentum coefficient (dshb)
    agg: AggregatorSpec = AggregatorSpec()
    byz: ByzantineConfig = ByzantineConfig()
    track_kappa_hat: bool = True
    #: In-scan robustness health taps (repro.obs.taps): computed inside
    #: the compiled step as pure side-outputs riding the metrics transfer.
    #: Static (frozen-dataclass jit key material) — tapped and untapped
    #: runs never share a compile.
    taps: bool = False
    worker_axes: Optional[tuple[str, ...]] = None   # spmd axes for vmap
    # Selective robustness (giant MoE; DESIGN.md §Arch-applicability):
    # params whose key-path matches get FSDP mean-gradients (no per-worker
    # copy ever exists) instead of the robust per-worker path.  Per-worker
    # state for 100B+ expert tables is Theta(n|theta|) and exceeds any
    # fixed pod — this is the deployable compromise, and it is reported.
    fsdp_keys: tuple[str, ...] = ()   # substring match on key paths


# TrainState is a plain dict pytree: params / momentum / opt_state / step.
TrainState = dict


def _split_info(params: PyTree, fsdp_keys: tuple[str, ...]):
    """Flattens params into (robust leaves, fsdp leaves) index lists."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    is_fsdp = [any(k in path for k in fsdp_keys) for path in paths]
    return treedef, paths, is_fsdp


def split_params(params: PyTree, fsdp_keys: tuple[str, ...]):
    treedef, _, is_fsdp = _split_info(params, fsdp_keys)
    leaves = treedef.flatten_up_to(params)
    robust = [l for l, f in zip(leaves, is_fsdp) if not f]
    fsdp = [l for l, f in zip(leaves, is_fsdp) if f]
    return robust, fsdp


def merge_params(robust: list, fsdp: list, treedef, is_fsdp: list) -> PyTree:
    it_r, it_f = iter(robust), iter(fsdp)
    leaves = [next(it_f) if f else next(it_r) for f in is_fsdp]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def init_state(params: PyTree, optimizer: Optimizer, n_workers: int,
               cfg: TrainerConfig) -> TrainState:
    state = dict(params=params, opt_state=optimizer.init(params),
                 step=jnp.zeros((), jnp.int32))
    if cfg.algorithm == "dshb":
        robust, _ = split_params(params, cfg.fsdp_keys)
        state["momentum"] = [
            jnp.zeros((n_workers,) + p.shape, jnp.float32) for p in robust]
    return state


def kappa_hat_masked(agg: PyTree, stack: PyTree, n_honest: Array,
                     internals: Optional[dict] = None) -> Array:
    """Eq. (26) with a TRACED honest count (fleet engine): the honest rows
    are selected by mask (row < n_honest) so per-lane Byzantine budgets can
    differ inside one compiled round.  ``internals`` stashes the per-leaf
    honest means + squared distance for the health taps, exactly as
    :func:`repro.core.theory.tree_kappa_hat` does."""
    num = jnp.zeros((), jnp.float32)
    den = jnp.zeros((), jnp.float32)
    cnt = jnp.maximum(n_honest.astype(jnp.float32), 1.0)
    for a, s in zip(jax.tree_util.tree_leaves(agg),
                    jax.tree_util.tree_leaves(stack)):
        x = s.astype(jnp.float32)
        n = x.shape[0]
        w = (jnp.arange(n) < n_honest).astype(jnp.float32)
        wl = w.reshape((-1,) + (1,) * (x.ndim - 1))
        mbar = (x * wl).sum(axis=0) / cnt
        if internals is not None:
            internals.setdefault("honest_mean_leaves", []).append(mbar)
        num += jnp.sum((a.astype(jnp.float32) - mbar) ** 2)
        sq = jnp.sum(((x - mbar) ** 2).reshape(n, -1), axis=1)
        den += (sq * w).sum() / cnt
    if internals is not None:
        internals["honest_sq_dist"] = num
    return jnp.sqrt(num / (den + 1e-20))


def build_train_step(loss_fn: Callable, optimizer: Optimizer,
                     cfg: TrainerConfig, lr_schedule: Callable
                     ) -> Callable:
    """Returns step(state, batch, key) -> (state, metrics).

    ``loss_fn(params, worker_batch) -> (scalar, metrics_dict)`` is the
    per-worker loss; ``batch`` carries a leading worker axis on every leaf.
    """
    spec = dataclasses.replace(cfg.agg, f=cfg.byz.f) \
        if cfg.agg.f != cfg.byz.f else cfg.agg

    vmap_kw = {}
    if cfg.worker_axes:
        vmap_kw["spmd_axis_name"] = cfg.worker_axes

    def step(state: TrainState, batch: PyTree, key: Array):
        params = state["params"]
        treedef, _, is_fsdp = _split_info(params, cfg.fsdp_keys)
        robust_p, fsdp_p = split_params(params, cfg.fsdp_keys)
        has_fsdp = any(is_fsdp)

        def loss_of(rp, fp, wbatch):
            merged = merge_params(rp, fp, treedef, is_fsdp)
            l, m = loss_fn(merged, wbatch)
            return l, m

        # Pass A: per-worker gradients of the robust subset (no psum).
        def grad_a(rp, fp, wbatch):
            (l, m), g = jax.value_and_grad(loss_of, argnums=0, has_aux=True)(
                rp, fp, wbatch)
            return l, g

        losses, grads = jax.vmap(grad_a, in_axes=(None, None, 0), **vmap_kw)(
            robust_p, fsdp_p, batch)
        grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        n_workers = losses.shape[0]
        n_honest = n_workers - cfg.byz.f

        # Pass B (giant-MoE FSDP subset): single backward of the mean loss;
        # expert gradients arrive pre-reduced over workers — per-worker
        # copies never materialize (DESIGN.md §3).
        if has_fsdp:
            def mean_loss(fp, rp, b):
                ls, _ = jax.vmap(lambda wb: loss_of(rp, fp, wb),
                                 **vmap_kw)(b)
                return ls.mean()
            fsdp_grads = jax.grad(mean_loss)(fsdp_p, robust_p, batch)
        else:
            fsdp_grads = []

        if cfg.algorithm == "dshb":
            beta = jnp.asarray(cfg.beta, jnp.float32)
            stack = jax.tree_util.tree_map(
                lambda m, g: beta * m + (1 - beta) * g,
                state["momentum"], grads)
            new_momentum = stack
        else:
            stack = grads
            new_momentum = None

        # Byzantine simulation: overwrite the last f rows.
        agg_key, key = jax.random.split(key)
        closure = (lambda t: robust_lib.robust_aggregate(t, spec, key=agg_key)) \
            if cfg.byz.attack.endswith("_opt") else None
        attacked = apply_attack_tree(cfg.byz.attack, stack, cfg.byz.f,
                                     eta=cfg.byz.eta, agg_closure=closure)

        tap_internals = {} if cfg.taps else None
        robust_dir = robust_lib.robust_aggregate(attacked, spec, key=agg_key,
                                                 internals=tap_internals)
        direction = merge_params(robust_dir, list(fsdp_grads), treedef, is_fsdp)

        lr = lr_schedule(state["step"])
        new_params, new_opt = optimizer.update(direction, state["opt_state"],
                                               params, lr)
        new_state = dict(params=new_params, opt_state=new_opt,
                         step=state["step"] + 1)
        if new_momentum is not None:
            # NOTE: Byzantine rows keep honest-computed momentum; their
            # transmitted values were attacked, not their local state —
            # matching the simulation protocol of the paper's code.
            new_state["momentum"] = new_momentum

        metrics = {
            "loss": losses[:n_honest].mean(),
            "lr": lr,
            "direction_norm": global_norm(direction),
        }
        if cfg.track_kappa_hat:
            # The honest rows of `stack` are those of `attacked`; reading
            # the stack lets the attacked copy die with the aggregation.
            metrics["kappa_hat"] = tree_kappa_hat(robust_dir, stack,
                                                  n_honest,
                                                  internals=tap_internals)
        if cfg.taps:
            from repro.obs import health_taps
            metrics["taps"] = health_taps(attacked, robust_dir,
                                          n_honest=n_honest, f=spec.f,
                                          rule=spec.rule, pre=spec.pre,
                                          internals=tap_internals)
        return new_state, metrics

    return step


# ---------------------------------------------------------------------------
# Convenience: full training loop for CPU-scale experiments.
# ---------------------------------------------------------------------------

def train_loop(loss_fn, params, batches, optimizer, cfg: TrainerConfig,
               lr_schedule, steps: int, *, seed: int = 0,
               eval_fn: Optional[Callable] = None, eval_every: int = 0,
               track_best: bool = True, engine: Optional[str] = None,
               chunk: Optional[int] = None,
               options: Optional[RoundOptions] = None):
    """Runs `steps` iterations; returns (final_params, history dict).

    Implements the paper's model selection: for D-GD, theta_hat is the
    iterate with the smallest aggregate norm (Alg. 1); history records
    everything needed for that selection and for accuracy curves.

    ``engine="scan"`` (default) compiles the whole step loop as chunked
    ``lax.scan`` programs (:mod:`repro.rounds`): batches and PRNG subkeys
    are stacked up front, metrics accumulate device-side, and the best-
    iterate selection runs in the scan carry — bit-for-bit the
    ``engine="loop"`` per-step jit loop (tested), minus R - 1 dispatches.
    ``chunk`` bounds the scan segment length (None = whole run between
    eval boundaries); the scan path also returns a ``"scan_report"`` with
    the engine's compile counters.

    ``options`` is the unified :class:`repro.rounds.RoundOptions` knob
    object; the ``engine=``/``chunk=`` keywords are back-compat shims that
    win when passed explicitly, and ``options.taps``/``options.backend``
    override ``cfg.taps`` / ``cfg.agg.backend``.
    """
    import numpy as np

    opts = resolve_options(options, engine=engine, chunk=chunk)
    cfg = opts.apply_config(cfg)
    engine, chunk = opts.engine_or_default, opts.chunk

    if opts.checkpoint is not None and engine != "scan":
        raise ValueError("options.checkpoint requires engine='scan' "
                         "(the loop path has no chunk boundaries to "
                         "snapshot at)")
    if engine == "loop":
        return _train_loop_loop(loss_fn, params, batches, optimizer, cfg,
                                lr_schedule, steps, seed=seed,
                                eval_fn=eval_fn, eval_every=eval_every,
                                track_best=track_best)
    if engine != "scan":
        raise ValueError(f"engine must be 'scan' or 'loop', got {engine!r}")

    from repro.rounds import (
        RoundEngine, cadence_boundaries, iterated_split_keys,
    )

    if steps == 0:
        first = next(batches) if hasattr(batches, "__next__") else batches
        n_workers = jax.tree_util.tree_leaves(first)[0].shape[0]
        state = init_state(params, optimizer, n_workers, cfg)
        return state["params"], {
            "history": {"loss": [], "direction_norm": [], "kappa_hat": [],
                        "eval": [], "eval_step": []},
            "best": {"norm": np.inf, "params": params, "acc": -np.inf},
            "state": state,
            "scan_report": {"trace_count": 0, "chunk_shapes": ()}}

    step_fn = build_train_step(loss_fn, optimizer, cfg, lr_schedule)
    if hasattr(batches, "__next__"):
        per_round = [next(batches) for _ in range(steps)]
        first = per_round[0]
        stacked = jax.tree_util.tree_map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]), *per_round)
    else:
        first = batches
        # One batch reused every step (the loop path's non-generator
        # semantics): a zero-copy broadcast view along the round axis.
        stacked = jax.tree_util.tree_map(
            lambda x: np.broadcast_to(np.asarray(x)[None],
                                      (steps,) + np.shape(x)), batches)
    n_workers = jax.tree_util.tree_leaves(first)[0].shape[0]
    state = init_state(params, optimizer, n_workers, cfg)
    keys = iterated_split_keys(jax.random.PRNGKey(seed), steps)

    def body(carry, op):
        state, best_norm, best_params = carry
        prev = state["params"]
        state, metrics = step_fn(state, op["batch"], op["key"])
        if track_best:
            dn = metrics["direction_norm"]
            better = dn < best_norm
            # theta_hat is the iterate ENTERING the best step (Alg. 1's
            # selection), hence prev, not the stepped params.
            best_params = jax.tree_util.tree_map(
                lambda new, old: jnp.where(better, new, old),
                prev, best_params)
            best_norm = jnp.where(better, dn, best_norm)
        return (state, best_norm, best_params), metrics

    hist: dict[str, list] = {"loss": [], "direction_norm": [], "kappa_hat": [],
                             "eval": [], "eval_step": []}
    best = {"norm": np.inf, "params": params, "acc": -np.inf}

    def on_boundary(end: int, carry):
        if eval_fn and eval_every and end % eval_every == 0 and end <= steps:
            acc = float(eval_fn(carry[0]["params"]))
            hist["eval"].append(acc)
            hist["eval_step"].append(end)
            best["acc"] = max(best["acc"], acc)

    eng = RoundEngine(body, chunk=chunk)
    carry0 = (state, jnp.asarray(np.inf, jnp.float32), params)

    # Resilience: resume from the last chunk-boundary snapshot (if any) and
    # keep snapshotting carry + metrics-so-far at every boundary.
    from repro.resilience import resolve_checkpoint
    ckpt_cfg = resolve_checkpoint(opts.checkpoint)
    checkpointer, start_round, saved_cols = None, 0, {}
    if ckpt_cfg is not None:
        from repro.resilience import (
            CarryCheckpointer, SnapshotStore, check_signature, restore_carry,
            restored_metrics,
        )
        store = SnapshotStore.from_config(ckpt_cfg)
        signature = {"surface": "trainer", "steps": steps, "chunk": chunk,
                     "seed": seed,
                     "eval_every": eval_every if eval_fn else 0}
        snap = store.load_latest() if ckpt_cfg.resume else None
        if snap is not None:
            start_round, arrays, meta = snap
            check_signature(meta["signature"], signature, store.path)
            carry0 = restore_carry(arrays, meta, carry0)
            saved_cols = restored_metrics(arrays)
            payload = meta.get("payload", {})
            hist["eval"] = list(payload.get("eval", []))
            hist["eval_step"] = [int(s) for s in payload.get("eval_step", [])]
            best["acc"] = float(payload.get("best_acc", -np.inf))
        checkpointer = CarryCheckpointer(
            store, signature=signature, total=steps, every=ckpt_cfg.every,
            base_columns=saved_cols,
            payload_fn=lambda end: {"eval": hist["eval"],
                                    "eval_step": hist["eval_step"],
                                    "best_acc": best["acc"]})

    (state, best_norm, best_params), metrics = eng.run(
        carry0, {"batch": stacked, "key": keys},
        boundaries=cadence_boundaries(steps, eval_every if eval_fn else 0),
        on_boundary=on_boundary,
        on_segment=checkpointer.on_segment if checkpointer else None,
        start=start_round)
    if checkpointer is not None:
        checkpointer.close()

    from repro.resilience import concat_metrics, metric_columns
    cols = (dict(saved_cols) if metrics is None
            else concat_metrics(saved_cols, metric_columns(metrics)))
    hist["loss"] = [float(x) for x in cols["loss"]]
    hist["direction_norm"] = [float(x) for x in cols["direction_norm"]]
    if "kappa_hat" in cols:
        hist["kappa_hat"] = [float(x) for x in cols["kappa_hat"]]
    tap_cols = {k[len("taps."):]: np.asarray(v) for k, v in cols.items()
                if k.startswith("taps.")}
    if tap_cols:
        # Aligned per-round tap columns: {field: (steps, ...) array}.
        hist["taps"] = tap_cols
    if track_best:
        best["norm"] = float(best_norm)
        best["params"] = best_params
    report = {"trace_count": eng.trace_count,
              "chunk_shapes": tuple(sorted(eng.chunk_shapes))}
    if ckpt_cfg is not None:
        report["snapshots"] = checkpointer.store.snapshots_written
        report["resumed_from"] = start_round
    return state["params"], {"history": hist, "best": best, "state": state,
                             "scan_report": report}


def _train_loop_loop(loss_fn, params, batches, optimizer, cfg: TrainerConfig,
                     lr_schedule, steps: int, *, seed: int = 0,
                     eval_fn: Optional[Callable] = None, eval_every: int = 0,
                     track_best: bool = True):
    """The per-step jitted Python loop — one dispatch + host round-trip per
    step.  The scan engine's parity baseline and the denominator of
    ``benchmarks/bench_convergence.py``'s rounds/sec speedup."""
    import numpy as np

    first = next(batches) if hasattr(batches, "__next__") else batches
    n_workers = jax.tree_util.tree_leaves(first)[0].shape[0]
    state = init_state(params, optimizer, n_workers, cfg)
    step_fn = jax.jit(build_train_step(loss_fn, optimizer, cfg, lr_schedule))
    key = jax.random.PRNGKey(seed)

    hist: dict[str, list] = {"loss": [], "direction_norm": [], "kappa_hat": [],
                             "eval": [], "eval_step": []}
    best = {"norm": np.inf, "params": params, "acc": -np.inf}
    tap_rows: list = []
    batch = first
    for t in range(steps):
        key, sub = jax.random.split(key)
        prev_params = state["params"]
        state, metrics = step_fn(state, batch, sub)
        hist["loss"].append(float(metrics["loss"]))
        dn = float(metrics["direction_norm"])
        hist["direction_norm"].append(dn)
        if "kappa_hat" in metrics:
            hist["kappa_hat"].append(float(metrics["kappa_hat"]))
        if "taps" in metrics:
            tap_rows.append(metrics["taps"].to_dict())
        if track_best and dn < best["norm"]:
            best["norm"], best["params"] = dn, prev_params
        if eval_fn and eval_every and (t + 1) % eval_every == 0:
            acc = float(eval_fn(state["params"]))
            hist["eval"].append(acc)
            hist["eval_step"].append(t + 1)
            best["acc"] = max(best["acc"], acc)
        if hasattr(batches, "__next__"):
            batch = next(batches)
    if tap_rows:
        fetched = jax.device_get(tap_rows)
        hist["taps"] = {k: np.stack([np.asarray(row[k]) for row in fetched])
                        for k in fetched[0]}
    return state["params"], {"history": hist, "best": best, "state": state}
