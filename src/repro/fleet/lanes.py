"""Lane-axis-polymorphic federated rounds: B scenarios in one jitted call.

``repro.fed.server`` runs ONE scenario per process, compiling one round per
attack family.  The fleet engine instead stacks B independent scenario jobs
("lanes") along a leading axis and vmaps a fully *dynamic* round over it:

* per-lane model params, optimizer state, client momentum stacks, and PRNG
  keys all live in one stacked state pytree;
* the attack FAMILY is a traced ``lax.switch`` index
  (:func:`repro.core.attacks.apply_attack_dyn`), eta / beta / local_lr /
  server lr are traced scalars, and the Byzantine counts go into
  :func:`repro.core.robust.robust_aggregate` as a traced ``f`` (rank
  masks in place of static slices);
* lanes whose job has finished are frozen by an ``active`` operand
  (``where(active, new, old)``) so shorter jobs ride along unchanged.

The result: a whole fleet costs ONE compile per *shape bucket* — the static
skeleton (cohort size, model arch, rule/pre, local-step count) — instead of
one compile per job x attack family.  What stays static is exactly the
bucket key material assembled in :mod:`repro.fleet.runner`.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import robust as robust_lib
from repro.core.attacks import apply_attack_dyn
from repro.core.theory import tree_kappa_hat
from repro.fed.clients import client_updates, gather_rows, scatter_rows
from repro.fed.poison import poison_batch
from repro.fed.server import FedConfig
from repro.optim import Optimizer, global_norm
from repro.robustness.guard import quarantine_stack
from repro.training.trainer import _split_info, merge_params

Array = jax.Array

#: Per-round, per-lane traced operands (each a scalar inside the vmap):
#:   attack_id  int32  — apply_attack_dyn branch index
#:   m_byz      int32  — Byzantine rows in the cohort stack
#:   f_agg      int32  — aggregator Byzantine budget (== m_byz)
#:   eta        float32 — attack strength
#:   beta       float32 — client momentum coefficient
#:   local_lr   float32 — client local-SGD step size
#:   lr         float32 — server learning rate this round
#:   active     bool   — False freezes the lane's state this round
#:   poison_rate     float32 — data-poisoning sample rate (0 = clean; the
#:                             poison KIND is static bucket_key material)
#:   poison_strength float32 — feature-poisoning noise scale
LANE_OP_FIELDS = ("attack_id", "m_byz", "f_agg", "eta", "beta", "local_lr",
                  "lr", "active", "poison_rate", "poison_strength")


def build_lane_round(loss_fn: Callable, optimizer: Optimizer,
                     cfg: FedConfig) -> Callable:
    """One lane's fully-dynamic round: ``(state, batch, idx, ops) ->
    (state, metrics)`` with every per-job quantity traced.

    ``cfg`` contributes only static skeleton (cohort size, local steps,
    algorithm, aggregation rule/pre); its ``f`` and the client beta /
    local_lr are ignored in favor of the traced ``ops`` values.
    """
    ccfg = cfg.client
    spec = cfg.agg

    def lane_round(state: dict, batch, idx: Array, ops: dict):
        params = state["params"]
        treedef, _, is_fsdp = _split_info(params, ())
        has_momentum = "momentum" in state
        key, agg_key = jax.random.split(state["key"])
        cohort_mom = gather_rows(state["momentum"], idx) \
            if has_momentum else []

        if cfg.poison is not None:
            # Same derived-key convention as repro.fed.server: rate and
            # strength are traced per-lane operands, only the KIND is
            # compile-relevant (bucket_key material in the runner).
            batch = poison_batch(batch, cfg.poison, ops["m_byz"],
                                 rate=ops["poison_rate"],
                                 strength=ops["poison_strength"],
                                 key=jax.random.fold_in(agg_key, 7))

        losses, stack, new_cohort_mom = client_updates(
            loss_fn, params, cohort_mom, batch, ccfg,
            beta=ops["beta"], local_lr=ops["local_lr"])
        m = losses.shape[0]
        m_honest = (m - ops["m_byz"]).astype(jnp.int32)

        attacked = apply_attack_dyn(ops["attack_id"], stack, ops["m_byz"],
                                    eta=ops["eta"])
        qinfo = None
        if cfg.guard is not None:
            attacked, qinfo = quarantine_stack(attacked, cfg.guard)
        tap_internals = {} if cfg.taps else None
        robust_dir = robust_lib.robust_aggregate(attacked, spec,
                                                 f=ops["f_agg"], key=agg_key,
                                                 internals=tap_internals)
        direction = merge_params(robust_dir, [], treedef, is_fsdp)

        lr = ops["lr"]
        new_params, new_opt = optimizer.update(
            direction, state["opt_state"], params, lr)
        new_state = dict(params=new_params, opt_state=new_opt,
                         step=state["step"] + 1, key=key)
        if has_momentum:
            new_state["momentum"] = scatter_rows(
                state["momentum"], idx, new_cohort_mom)

        w = (jnp.arange(m) < m_honest).astype(jnp.float32)
        metrics = {
            "loss": (losses * w).sum() / jnp.maximum(
                m_honest.astype(jnp.float32), 1.0),
            "lr": lr,
            "direction_norm": global_norm(direction),
        }
        if qinfo is not None:
            metrics["quarantined_count"] = qinfo["count"]
        if cfg.track_kappa_hat:
            metrics["kappa_hat"] = tree_kappa_hat(robust_dir, attacked,
                                                  m_honest,
                                                  internals=tap_internals)
        if cfg.taps:
            from repro.obs import health_taps
            # f_agg / m_honest are traced per-lane scalars: the same
            # rank-mask selection as the aggregation.
            metrics["taps"] = health_taps(
                attacked, robust_dir, n_honest=m_honest, f=ops["f_agg"],
                rule=spec.rule, pre=spec.pre,
                internals=tap_internals, quarantine=qinfo)

        # Finished lanes ride along bit-identically frozen.
        frozen = jax.tree_util.tree_map(
            lambda new, old: jnp.where(ops["active"], new, old),
            new_state, state)
        return frozen, metrics

    return lane_round


def build_fleet_round(loss_fn: Callable, optimizer: Optimizer,
                      cfg: FedConfig, *,
                      on_trace: Optional[Callable[[], None]] = None
                      ) -> Callable:
    """The jitted B-lane round: vmap of :func:`build_lane_round` over a
    leading lane axis on state / batch / cohort ids / ops.

    ``on_trace`` fires at TRACE time (not per call) — the runner uses it to
    assert the one-compile-per-shape-bucket contract.
    """
    lane = build_lane_round(loss_fn, optimizer, cfg)

    def fleet_round(state: dict, batch, idx: Array, ops: dict):
        if on_trace is not None:
            on_trace()
        return jax.vmap(lane)(state, batch, idx, ops)

    return jax.jit(fleet_round)


def donation_supported() -> bool:
    """Whether the active backend honors ``donate_argnums`` (CPU jax
    ignores it with a warning per call — so the continuous service only
    requests donation off-CPU)."""
    return jax.default_backend() != "cpu"


def build_lane_admit(*, donate: bool = False) -> Callable:
    """The continuous service's slot writer: ``admit(state, lane_state,
    slot) -> state`` overwrites lane ``slot`` of the stacked state with a
    fresh job's (unstacked) init state.

    ``slot`` is a TRACED index (``lax.dynamic_update_index_in_dim``), so
    one compile covers every slot of a bucket shape — admission never
    retraces, which is what keeps mid-run admission O(chunk boundary)
    instead of O(compile).  With ``donate=True`` the stacked state buffer
    is donated, so admitting into a multi-MB bucket updates in place
    rather than reallocating it.
    """
    def admit(state: dict, lane_state: dict, slot: Array):
        return jax.tree_util.tree_map(
            lambda full, one: jax.lax.dynamic_update_index_in_dim(
                full, one, slot, 0),
            state, lane_state)

    return jax.jit(admit, donate_argnums=(0,) if donate else ())


def build_fleet_scan(loss_fn: Callable, optimizer: Optimizer,
                     cfg: FedConfig, *,
                     on_trace: Optional[Callable[[], None]] = None,
                     donate: bool = False) -> Callable:
    """The scanned fleet program: ``lax.scan`` of the vmapped B-lane round
    over a leading ROUND axis — B lanes x K rounds in one compiled call.

    ``(state, operands) -> (state, metrics)`` where ``operands`` is
    ``{"batch": (K, B, m, L, ...), "idx": (K, B, m), "ops": {field:
    (K, B)}}`` (one segment's slice of the runner's precomputed round
    plan) and ``metrics`` leaves come back round-stacked ``(K, B)``.
    Scanning outside the vmap keeps the per-round math identical to
    :func:`build_fleet_round` — a scanned lane is bit-for-bit the stepped
    lane (tested) — while collapsing K dispatches + K metric fetches into
    one.  ``on_trace`` fires at TRACE time; each distinct segment length
    K is one trace of this program.

    ``donate=True`` donates the carry state buffer (the continuous
    service's steady-state: the bucket state is rewritten every chunk, so
    holding the stale copy alive doubles resident state for nothing).
    Donation changes buffer aliasing only, never math — the scanned
    result stays bit-for-bit.
    """
    lane = build_lane_round(loss_fn, optimizer, cfg)

    def fleet_scan(state: dict, operands: dict):
        if on_trace is not None:
            on_trace()

        def step(st, op):
            return jax.vmap(lane)(st, op["batch"], op["idx"], op["ops"])

        return jax.lax.scan(step, state, operands)

    return jax.jit(fleet_scan, donate_argnums=(0,) if donate else ())
