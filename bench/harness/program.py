"""The system under test: the program's robust train step, built from a
configuration file and a traffic mix the way its training entry point builds it
(``repro.launch.train.Run``: ``build_train_step`` jitted with the state
donated), with weights made on the device in one jitted call from the
seed.

Only this module and the traffic feed import the program.
"""
from __future__ import annotations

import argparse
import dataclasses
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


def program_config(config: dict):
    """The program's ModelConfig for a configuration file: the program's
    own config of that id, with every size the file gives."""
    from repro.configs import get_config

    base = get_config(config["program"])
    changed = {k: v for k, v in config["sizes"].items()
               if getattr(base, k) != v}
    cfg = base.replace(**changed) if changed else base
    if jnp.dtype(cfg.dtype).name != config["dtype"]:
        raise SystemExit(f"{config['name']}: the program computes in "
                         f"{jnp.dtype(cfg.dtype).name}, the file states "
                         f"{config['dtype']}")
    return cfg


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


@dataclasses.dataclass
class Program:
    init: Callable           # jitted: key -> step-0 train state
    step: Callable           # jitted train step, state donated
    leaf_paths: list         # robust leaves, in the momentum's order
    leaf_widths: list        # their numbers of values


def build(config: dict, traffic: dict, batches, *, seed: int,
          loss_wrap: Optional[Callable] = None,
          step_wrap: Optional[Callable] = None) -> Program:
    """The program's train step and initializer for one cell.

    ``loss_wrap`` / ``step_wrap`` plant a fault under the timed path
    (tests only): they wrap the model's per-worker loss and the built
    step function."""
    from repro.launch.train import Run, parse_agg
    from repro.models import build_model
    from repro.optim import sgd
    from repro.optim.schedules import constant
    from repro.training import (ByzantineConfig, TrainerConfig,
                                build_train_step, init_state)

    cfg = program_config(config)
    n, f = int(traffic["workers"]), int(traffic["byz"])
    agg = parse_agg(traffic["agg"])
    tcfg = TrainerConfig(
        algorithm="dshb", beta=float(traffic["beta"]),
        agg=dataclasses.replace(agg, f=f, backend=traffic["backend"]),
        byz=ByzantineConfig(f=f, attack=traffic["attack"]))
    args = argparse.Namespace(seed=seed, workers=n, byz=f,
                              attack=traffic["attack"], agg=traffic["agg"])
    run = Run(args=args, cfg=cfg, model=build_model(cfg), tcfg=tcfg,
              optimizer=sgd(clip=float(traffic["clip"])),
              schedule=constant(float(traffic["lr"])), batches=batches)
    if loss_wrap is None and step_wrap is None:
        step = run.step_fn()
    else:
        loss = run.model.loss if loss_wrap is None else loss_wrap(run.model.loss)
        fn = build_train_step(loss, run.optimizer, tcfg, run.schedule)
        step = jax.jit(fn if step_wrap is None else step_wrap(fn),
                       donate_argnums=0)

    def init(key):
        return init_state(run.model.init(key), run.optimizer, n, tcfg)

    shapes = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(run.model.init, jax.random.PRNGKey(0)))[0]
    return Program(init=jax.jit(init), step=step,
                   leaf_paths=[jax.tree_util.keystr(p) for p, _ in shapes],
                   leaf_widths=[math.prod(x.shape) for _, x in shapes])


def check_dispatch(require_compiled: bool) -> str:
    """The kernel dispatch record of the traced step: the Pallas backend,
    compiled (not interpreted) where ``require_compiled``, no fallback.
    Returns its description; raises SystemExit otherwise."""
    from repro.kernels.dispatch import last_dispatch

    rec = last_dispatch()
    if rec is None:
        raise SystemExit("no kernel dispatch was recorded")
    text = rec.describe()
    bad = []
    if rec.backend != "pallas":
        bad.append(f"backend {rec.backend!r}, not 'pallas'")
    if require_compiled and any("-interpret" in d.used
                                for d in rec.decisions):
        bad.append("a kernel ran in interpret mode")
    if rec.fallbacks:
        bad.append(f"kernel fallbacks {rec.fallbacks}")
    if bad:
        raise SystemExit("dispatch: " + "; ".join(bad) + "\n" + text)
    return text


@jax.jit
def stack_row_norms(stack: list) -> jax.Array:
    """(n, leaves) L2 norm of every worker row of every stacked leaf."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)
                                    .astype(jnp.float32)), axis=1))
        for x in stack], axis=1)


@jax.jit
def leaf_diff_norms(a, b) -> jax.Array:
    """(leaves,) L2 norm of a - b, leaf by leaf, in float32."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))])


def first_steps(step, state, key, sub, batch, feed, paths: list, *,
                beta: float, compared: int):
    """Drive the compiled ``step`` through ``compared`` + 1 steps from the
    step-0 ``state``, with ``batch`` and ``sub`` for the first and the
    feed and ``key`` after it: the window's own call and feed.  Returns
    (state, key, readings, the compared steps' batches).  The readings
    are each compared step's loss and direction norm, every worker's first
    gradient norm per leaf (from the momentum after step 1, m =
    (1 - beta) g) and every leaf's change over the compared steps."""
    start = jax.device_get(state["params"])
    read = {"paths": paths, "loss": [], "direction_norm": []}
    batches = []
    for t in range(compared + 1):
        if t:
            key, sub = jax.random.split(key)
            batch = next(feed)
        if t < compared:
            batches.append(batch)
        state, metrics = step(state, jax.device_put(batch), sub)
        m = jax.device_get(metrics)
        if t < compared:
            read["loss"].append(float(m["loss"]))
            read["direction_norm"].append(float(m["direction_norm"]))
        if t == 0:
            read["grad_norms"] = host(stack_row_norms(state["momentum"])) \
                / (1 - beta)
        if t == compared - 1:
            before = jax.device_put(start)
            read["change_norms"] = host(leaf_diff_norms(state["params"],
                                                        before))
            del before
    jax.block_until_ready(state)
    return state, key, read, batches


def host(x) -> np.ndarray:
    return np.asarray(jax.device_get(x), np.float64)
