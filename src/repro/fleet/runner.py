"""Fleet runner: pack scenario jobs into shape buckets, step them in
lockstep, demux per-lane histories.

The host-side half of the fleet engine (:mod:`repro.fleet.lanes` is the
device half).  A :class:`FleetJob` is a fully-materialized federated run —
config, loss, initial params, batch function, schedules; a
:class:`ScenarioSpec` names a registry scenario + seed and materializes to
a job.  The runner groups jobs whose *static skeleton* matches into lane
buckets (one compile each), stacks their states, and drives every bucket
round-by-round with per-lane traced operands — per-round host work is the
same cohort sampling / batch building the single-scenario loop does, but
the device sees ONE dispatch per bucket per round instead of one per job.

``max_lanes=1`` degrades to the sequential per-job loop over the identical
compiled round — the baseline `benchmarks/bench_fleet.py` measures against
(compiles are shared across equal-shape buckets, so it stays one compile).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.attacks import dyn_attack_id
from repro.core.bucketing import default_bucket_size
from repro.data import build_heterogeneous, make_classification
from repro.fed.clients import init_client_momentum
from repro.fed.metrics import FedHistory
from repro.fed.poison import static_signature as poison_signature
from repro.fed.schedules import AttackSchedule, FixedByzantine
from repro.fed.scenarios import (
    Scenario, _mlp_eval, _mlp_init, _mlp_loss, cohort_batch_fn, get_scenario,
)
from repro.fed.server import FedConfig, rescale_f, sample_cohort
from repro.fleet.lanes import build_fleet_scan
from repro.obs import runtime as obs_runtime
from repro.optim import Optimizer, sgd
from repro.rounds import (
    RoundOptions, cadence_boundaries, resolve_options, split_segments,
    stack_rounds,
)

PyTree = Any

#: Attack eta defaults mirrored from the static path
#: (`apply_attack_tree`): used when a schedule phase leaves eta unset.
_ETA_DEFAULTS = {"alie": 1.0, "foe": 2.0}

#: Shared server optimizer for scenario-derived jobs.  One OBJECT, not one
#: per job: the optimizer is bucket-key material (lanes sharing a compiled
#: round must share its update closure).
SCENARIO_OPTIMIZER = sgd(clip=2.0)


@dataclasses.dataclass(frozen=True)
class ScenarioSpec:
    """A registry scenario + the per-job knobs: one fleet lane, declaratively.

    ``scenario`` is a registry name or an inline :class:`Scenario`;
    ``rounds`` overrides the scenario's round count (lanes of different
    lengths share a bucket — shorter ones freeze when done).
    """
    scenario: Union[str, Scenario]
    seed: int = 0
    rounds: Optional[int] = None
    label: Optional[str] = None


@dataclasses.dataclass
class FleetJob:
    """A fully-materialized federated run, ready to be packed into a lane.

    Jobs grouped into one bucket MUST share ``loss_fn`` and ``optimizer``
    *objects* (they become part of the compiled round); everything that can
    differ per lane — f, attack schedule, identity schedule, seed, rounds,
    beta, local_lr, server lr — is carried as traced operands.
    """
    label: str
    cfg: FedConfig
    loss_fn: Callable
    optimizer: Optimizer
    params: PyTree
    batch_fn: Callable
    rounds: int
    seed: int = 0
    schedule: AttackSchedule = dataclasses.field(
        default_factory=AttackSchedule)
    byz_identity: Any = None
    lr_fn: Callable[[int], float] = lambda r: 0.1
    eval_fn: Optional[Callable] = None
    eval_every: int = 0

    def __post_init__(self):
        if self.byz_identity is None:
            self.byz_identity = FixedByzantine(self.cfg.n_clients, self.cfg.f)
        if self.cfg.agg.rule == "mda":
            raise ValueError(
                "mda has no traced-f form; fleet lanes cannot run it "
                "(use the single-scenario engine instead)")
        for phase in self.schedule.phases:
            dyn_attack_id(phase.attack)   # raises for _opt / unknown
        if (self.cfg.agg.pre == "bucketing"
                and self.cfg.agg.bucket_size is None):
            raise ValueError(
                "fleet lanes with pre='bucketing' need an explicit "
                "bucket_size (resolve it host-side, e.g. "
                "default_bucket_size(m, f_round))")
        if ((self.cfg.agg.hier or self.cfg.agg.backend == "pallas_hier")
                and self.cfg.agg.bucket_size is None):
            raise ValueError(
                "hierarchical fleet lanes need an explicit bucket_size "
                "(lanes pass a traced f, whose floor(n/2f) default "
                "is shape-level); resolve it host-side, e.g. "
                "default_bucket_size(m, f_round)")

    @property
    def m_byz(self) -> int:
        cfg = self.cfg
        return rescale_f(cfg.f, cfg.n_clients, cfg.clients_per_round)


def job_from_spec(spec: ScenarioSpec, *, dim: int = 48,
                  n_samples: int = 9000, noise: float = 1.6) -> FleetJob:
    """Materialize a registry scenario into a :class:`FleetJob`.

    Mirrors ``repro.fed.scenarios.build_scenario`` (same synthetic task,
    same Dirichlet shards) but routes through the fleet's shared optimizer
    object and resolves the bucketing bucket size host-side.
    """
    sc = get_scenario(spec.scenario) if isinstance(spec.scenario, str) \
        else spec.scenario
    seed = spec.seed
    x, y = make_classification(n_samples, 10, dim, noise=noise, seed=seed)
    split = (n_samples * 2) // 3
    ds = build_heterogeneous({"x": x[:split], "y": y[:split]}, "y",
                             sc.n_clients, alpha=sc.alpha, seed=seed)
    xt, yt = x[split:], y[split:]

    cfg = sc.fed_config()
    if cfg.agg.pre == "bucketing" and cfg.agg.bucket_size is None:
        m = cfg.clients_per_round
        bs = default_bucket_size(m, rescale_f(cfg.f, cfg.n_clients, m))
        cfg = dataclasses.replace(
            cfg, agg=dataclasses.replace(cfg.agg, bucket_size=bs))

    server_lr = sc.server_lr
    return FleetJob(
        label=spec.label or f"{sc.name}:s{seed}",
        cfg=cfg,
        loss_fn=_mlp_loss,
        optimizer=SCENARIO_OPTIMIZER,
        params=_mlp_init(jax.random.PRNGKey(seed), dim),
        batch_fn=cohort_batch_fn(ds, sc.batch_size, sc.local_steps),
        rounds=spec.rounds if spec.rounds is not None else sc.rounds,
        seed=seed,
        schedule=sc.attack,
        byz_identity=sc.byz_identity(),
        lr_fn=lambda r: server_lr,
        eval_fn=_mlp_eval(xt, yt))


def apply_job_options(job: FleetJob, options: RoundOptions) -> FleetJob:
    """``job`` with the options' taps/backend overrides applied to its
    config.  Returns the SAME object for the no-op options so bucket keys
    (which hash the config fields) and any caller-held references agree."""
    cfg = options.apply_config(job.cfg)
    return job if cfg is job.cfg else dataclasses.replace(job, cfg=cfg)


# ---------------------------------------------------------------------------
# Per-lane planning/state primitives — shared by the batch runner and the
# continuous service, so the two paths are the same math by construction.
# ---------------------------------------------------------------------------

def plan_lane_round(job: FleetJob, r: int, rng: np.random.Generator
                    ) -> tuple[Any, np.ndarray, dict, tuple]:
    """HOST: one lane's decisions for its LOCAL round ``r``.

    Consumes ``rng`` exactly like the single-scenario loop (cohort sample,
    then batch build) — the rng is the LANE's own stream seeded from
    ``job.seed``, so a lane's plan depends only on its own round index,
    never on which other lanes share the bucket or when it was admitted.
    That independence is what makes mid-run admission bit-safe.

    Returns ``(batch, cohort, ops, meta)``; ``meta`` is the
    ``(attack, raw_eta, cohort)`` triple the history demux records.
    """
    cfg = job.cfg
    m_byz = job.m_byz
    attack, eta = job.schedule.resolve(r)
    cohort = sample_cohort(rng, cfg.n_clients, cfg.clients_per_round,
                           job.byz_identity.ids(r), m_byz)
    n_flip = m_byz if attack == "lf" else 0
    batch = job.batch_fn(cohort, n_flip, rng)
    ops = {"attack_id": dyn_attack_id(attack),
           "m_byz": m_byz, "f_agg": m_byz,
           "eta": eta if eta is not None else _ETA_DEFAULTS.get(attack, 0.0),
           "beta": cfg.client.beta, "local_lr": cfg.client.local_lr,
           "lr": float(job.lr_fn(r)), "active": r < job.rounds,
           # Poison rate/strength are per-lane data; the poison KIND is
           # static (bucket_key).  rate=0 on a poison-compiled bucket is a
           # clean lane — that is what lets one bucket sweep a rate grid.
           "poison_rate": cfg.poison.rate if cfg.poison else 0.0,
           "poison_strength": cfg.poison.strength if cfg.poison else 0.0}
    return batch, cohort, ops, (attack, eta, cohort)


def init_lane_state(job: FleetJob) -> dict:
    """One lane's (unstacked) device state at round 0 — identical to the
    single-scenario engine's init for the same job."""
    st = dict(params=job.params,
              opt_state=job.optimizer.init(job.params),
              step=jnp.zeros((), jnp.int32),
              key=jax.random.PRNGKey(job.seed))
    if job.cfg.client.algorithm == "dshb":
        st["momentum"] = init_client_momentum(job.params,
                                              job.cfg.n_clients)
    return st


def lane_filler(job: FleetJob) -> tuple[Any, np.ndarray, dict]:
    """Per-round operands for an UNOCCUPIED lane slot, shaped like
    ``job``'s real operands (the slot template job fixes the bucket's
    shapes): zeroed batch, cohort 0s, attack "none", ``active=False``.

    ``active=False`` freezes the slot's state via ``where``, so whatever
    the filler computes is discarded elementwise — the values only need
    to be finite-shaped, not meaningful.  Filler rounds consume NO rng:
    an empty slot has no lane stream to perturb.
    """
    m = job.cfg.clients_per_round
    probe = job.batch_fn(np.arange(m, dtype=np.int32), 0,
                         np.random.default_rng(0))
    batch = jax.tree_util.tree_map(
        lambda a: np.zeros_like(np.asarray(a)), probe)
    idx = np.zeros((m,), np.int32)
    ops = {"attack_id": dyn_attack_id("none"), "m_byz": 0, "f_agg": 0,
           "eta": 0.0, "beta": 0.0, "local_lr": 0.0, "lr": 0.0,
           "active": False, "poison_rate": 0.0, "poison_strength": 0.0}
    return batch, idx, ops


#: Lane-operand field dtypes — the packing contract with
#: :data:`repro.fleet.lanes.LANE_OP_FIELDS`.
_OP_DTYPES = {"attack_id": np.int32, "m_byz": np.int32, "f_agg": np.int32,
              "eta": np.float32, "beta": np.float32, "local_lr": np.float32,
              "lr": np.float32, "active": bool,
              "poison_rate": np.float32, "poison_strength": np.float32}


def _pack_round(batches: list, cohorts: list, ops: dict[str, list]) -> dict:
    """Stack one round's per-lane plans into the ``(B, ...)`` operand dict
    the vmapped lane round consumes."""
    return {
        "batch": jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches),
        "idx": np.stack(cohorts).astype(np.int32),
        "ops": {f: np.asarray(ops[f], dt) for f, dt in _OP_DTYPES.items()},
    }


# ---------------------------------------------------------------------------
# Shape bucketing + compile cache.
# ---------------------------------------------------------------------------

def _tree_sig(tree: PyTree) -> tuple:
    """Hashable structure+shape+dtype signature of a pytree."""
    flat, treedef = jax.tree_util.tree_flatten(tree)
    return (str(treedef),) + tuple(
        (tuple(np.shape(leaf)),
         str(getattr(leaf, "dtype", None) or np.asarray(leaf).dtype))
        for leaf in flat)


def _mesh_sig() -> tuple:
    """Hashable fingerprint of the mesh the aggregation stage would shard
    over at trace time.

    The kernel-backend routing (notably "pallas_sharded" and "auto" —
    including their recorded degrades) is baked into the compiled round,
    so two drains under different meshes / device counts must never share
    a compile-cache entry.  Mirrors ``kernels.dispatch.resolve_shard_mesh``
    without touching device state when nothing changed."""
    from repro.launch.mesh import current_mesh
    mesh = current_mesh()
    if mesh is not None:
        return (jax.device_count(), tuple(mesh.axis_names),
                tuple(mesh.shape.values()))
    return (jax.device_count(),)


def bucket_key(job: FleetJob, *, chunk: Optional[int] = None) -> tuple:
    """The static skeleton a compiled fleet round is specialized on.

    Everything NOT here — f, attack family, eta, beta, local_lr, lr, seed,
    round count — is a traced per-lane operand.  ``chunk`` is the runner's
    scan segment length: two runners scanning the same jobs at different
    cadences compile different programs, so the chunk is key material —
    compiles must never leak across cadences.
    """
    c = job.cfg
    probe = job.batch_fn(
        np.arange(c.clients_per_round, dtype=np.int32), 0,
        np.random.default_rng(0))
    return (c.n_clients, c.clients_per_round,
            c.client.local_steps, c.client.algorithm,
            c.agg.rule, c.agg.pre, c.agg.bucket_size, c.agg.hier,
            c.agg.gm_iters, c.agg.gm_eps,
            c.agg.autogm_lamb, c.agg.autogm_iters,
            c.agg.transport_dtype, c.agg.sketch_dim,
            c.agg.backend, _mesh_sig(),
            c.track_kappa_hat, c.taps,
            poison_signature(c.poison), c.guard,
            job.loss_fn, job.optimizer,
            _tree_sig(job.params), _tree_sig(probe), chunk)


@dataclasses.dataclass
class LaneBucket:
    key: tuple
    jobs: list[FleetJob]
    indices: list[int]          # positions in the submitted job list


@dataclasses.dataclass
class FleetResult:
    """One lane's demuxed outcome."""
    label: str
    job: FleetJob
    state: dict                 # final (unstacked) lane state
    history: FedHistory
    evals: list[tuple[int, float]] = dataclasses.field(default_factory=list)
    best_eval: Optional[float] = None


class FleetRunner:
    """Packs jobs into shape buckets and scans each bucket in lockstep.

    Each bucket runs as B lanes x R rounds of ONE compiled scan program
    (``repro.fleet.lanes.build_fleet_scan``): the whole per-round host loop
    — schedule resolution, cohort sampling, batch building, operand
    packing — happens up front, and the device sees one dispatch per scan
    segment instead of one per round.  ``chunk`` bounds the segment length
    (None = whole run, cut only at eval boundaries).

    The compile cache is keyed on (bucket static key incl. chunk, lane
    count): re-running the same runner, or many max_lanes-sized chunks of
    one bucket, reuses the compiled program.  ``trace_count`` counts actual
    tracings — one per bucket x lane-count x SEGMENT LENGTH, the
    one-compile-per-(bucket x chunk-shape) contract benchmarks assert on.
    """

    def __init__(self, jobs: Sequence[Union[FleetJob, ScenarioSpec]], *,
                 max_lanes: Optional[int] = None,
                 compile_cache: Optional[dict] = None,
                 chunk: Optional[int] = None,
                 options: Optional[RoundOptions] = None):
        # Unified knob resolution: an explicit ``chunk=`` wins over
        # ``options.chunk`` (the shim rule); taps/backend overrides are
        # applied to every job's config BEFORE packing so they land in the
        # bucket key.  The fleet is scan-only, so ``engine`` is ignored.
        opts = resolve_options(options, chunk=chunk)
        self.options = opts
        self.jobs = [apply_job_options(
                         job_from_spec(j) if isinstance(j, ScenarioSpec)
                         else j, opts)
                     for j in jobs]
        if not self.jobs:
            raise ValueError("empty fleet")
        self.max_lanes = max_lanes
        self.chunk = opts.chunk
        # ``compile_cache`` may be shared across runners (FleetService
        # passes one per service) so later fleets reuse earlier compiles;
        # ``trace_count`` still counts only THIS runner's new tracings
        # (a cached program retracing on a NEW segment length attributes
        # to the runner that built it).
        self._compiled: dict[tuple, Callable] = \
            compile_cache if compile_cache is not None else {}
        self.trace_count = 0
        self._buckets = self._pack()

    # -- packing ----------------------------------------------------------
    def _pack(self) -> list[LaneBucket]:
        groups: dict[tuple, LaneBucket] = {}
        for i, job in enumerate(self.jobs):
            key = bucket_key(job, chunk=self.chunk)
            if key not in groups:
                groups[key] = LaneBucket(key, [], [])
            groups[key].jobs.append(job)
            groups[key].indices.append(i)
        buckets: list[LaneBucket] = []
        for g in groups.values():
            cap = self.max_lanes or len(g.jobs)
            for s in range(0, len(g.jobs), cap):
                buckets.append(LaneBucket(g.key, g.jobs[s:s + cap],
                                          g.indices[s:s + cap]))
        return buckets

    @property
    def n_buckets(self) -> int:
        """Distinct shape buckets (not max_lanes chunks)."""
        return len({b.key for b in self._buckets})

    def _round_fn(self, bucket: LaneBucket) -> Callable:
        cache_key = (bucket.key, len(bucket.jobs))
        if cache_key not in self._compiled:
            job0 = bucket.jobs[0]
            lanes = len(bucket.jobs)

            def bump():
                self.trace_count += 1
                obs_runtime.event("fleet.trace", lanes=lanes,
                                  trace_count=self.trace_count)

            self._compiled[cache_key] = build_fleet_scan(
                job0.loss_fn, job0.optimizer, job0.cfg, on_trace=bump)
        return self._compiled[cache_key]

    # -- execution --------------------------------------------------------
    def run(self) -> list[FleetResult]:
        """Run every job to completion; results in submission order."""
        results: list[Optional[FleetResult]] = [None] * len(self.jobs)
        for bi, bucket in enumerate(self._buckets):
            for idx, res in zip(bucket.indices,
                                self._run_bucket(bucket, bucket_index=bi)):
                results[idx] = res
        return results  # type: ignore[return-value]

    def _plan_bucket(self, bucket: LaneBucket
                     ) -> tuple[dict, list[tuple[list, list, list]]]:
        """HOST, once per bucket run: the whole per-round decision loop —
        schedule resolution, cohort sampling, batch building, lane-operand
        packing — resolved into round-stacked scan operands.

        Returns ``(operands, round_meta)``: operands leaves are
        ``(R, B, ...)`` arrays, ``round_meta[r]`` is the (attacks,
        raw etas, cohorts) triple the history demux records.  The host rng
        consumption order is exactly the old per-round loop's (cohort
        sample then batch build, lane by lane, round by round), so scanned
        cohorts/batches match the stepped engine's sample for sample.
        """
        jobs = bucket.jobs
        rngs = [np.random.default_rng(job.seed) for job in jobs]
        max_rounds = max(job.rounds for job in jobs)

        per_round: list[dict] = []
        round_meta: list[tuple[list, list, list]] = []
        for r in range(max_rounds):
            attacks, etas_raw, cohorts, batches = [], [], [], []
            ops: dict[str, list] = {k: [] for k in _OP_DTYPES}
            for k, job in enumerate(jobs):
                batch, cohort, lane_ops, (attack, eta, _) = \
                    plan_lane_round(job, r, rngs[k])
                batches.append(batch)
                attacks.append(attack)
                etas_raw.append(eta)
                cohorts.append(cohort)
                for f in _OP_DTYPES:
                    ops[f].append(lane_ops[f])
            per_round.append(_pack_round(batches, cohorts, ops))
            round_meta.append((attacks, etas_raw, cohorts))
        return stack_rounds(per_round), round_meta

    def _run_bucket(self, bucket: LaneBucket, *,
                    bucket_index: int = 0) -> list[FleetResult]:
        jobs = bucket.jobs
        fleet_scan = self._round_fn(bucket)

        state = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[init_lane_state(job) for job in jobs])

        m_byzs = [job.m_byz for job in jobs]
        hists = [FedHistory() for _ in jobs]
        evals: list[list[tuple[int, Any]]] = [[] for _ in jobs]
        max_rounds = max(job.rounds for job in jobs)
        if max_rounds == 0:             # degenerate: nothing to scan
            return [FleetResult(label=job.label, job=job,
                                state=jax.tree_util.tree_map(
                                    lambda leaf, kk=k: leaf[kk], state),
                                history=hists[k], evals=[])
                    for k, job in enumerate(jobs)]
        operands, round_meta = self._plan_bucket(bucket)

        # Resilience: per-bucket snapshot subdir; the host plan above was
        # recomputed in full, so only the stacked carry + metrics columns +
        # eval points need restoring.
        from repro.resilience import resolve_checkpoint
        ckpt_cfg = resolve_checkpoint(self.options.checkpoint)
        checkpointer, start_round, saved_cols = None, 0, {}
        if ckpt_cfg is not None:
            from repro.resilience import (
                CarryCheckpointer, SnapshotStore, check_signature,
                restore_carry, restored_metrics,
            )
            store = SnapshotStore.from_config(
                ckpt_cfg, subdir=f"bucket-{bucket_index:03d}")
            signature = {"surface": "fleet",
                         "labels": [j.label for j in jobs],
                         "rounds": [j.rounds for j in jobs],
                         "seeds": [j.seed for j in jobs],
                         "chunk": self.chunk}
            snap = store.load_latest() if ckpt_cfg.resume else None
            if snap is not None:
                start_round, arrays, snap_meta = snap
                check_signature(snap_meta["signature"], signature, store.path)
                state = restore_carry(arrays, snap_meta, state)
                saved_cols = restored_metrics(arrays)
                for k, lane in enumerate(
                        snap_meta.get("payload", {}).get("evals", [])):
                    evals[k] = [(int(r), float(v)) for r, v in lane]
            checkpointer = CarryCheckpointer(
                store, signature=signature, total=max_rounds,
                every=ckpt_cfg.every, base_columns=saved_cols,
                payload_fn=lambda end: {
                    "evals": [[(int(r), float(v)) for r, v in lane]
                              for lane in evals]})

        # Scan segments are cut at every eval round so the carry state is
        # back on the host exactly when the stepped loop evaluated it.
        boundaries = cadence_boundaries(
            max_rounds, *(job.eval_every for job in jobs
                          if job.eval_fn is not None and job.eval_every))
        seg_metrics: list[dict] = []
        for start, end in split_segments(max_rounds, self.chunk, boundaries):
            if end <= start_round:      # already executed before the resume
                continue
            seg_ops = jax.tree_util.tree_map(lambda a: a[start:end], operands)
            with obs_runtime.span("fleet.segment", start=start, end=end,
                                  lanes=len(jobs)):
                state, metrics = fleet_scan(state, seg_ops)
            seg_metrics.append(metrics)
            for k, job in enumerate(jobs):
                if (job.eval_fn is not None and job.eval_every
                        and end <= job.rounds
                        and end % job.eval_every == 0):
                    lane_params = jax.tree_util.tree_map(
                        lambda leaf, kk=k: leaf[kk], state["params"])
                    # Keep the device scalar: float() here would sync the
                    # dispatch pipeline per eval (same reason the round
                    # metrics stay on device until the demux below).
                    evals[k].append((end, job.eval_fn(lane_params)))
            if checkpointer is not None:
                checkpointer.on_segment(start, end, state, metrics)
        if checkpointer is not None:
            checkpointer.close()

        # Demux: one host transfer for the whole run's metrics + evals.
        from repro.resilience import concat_metrics, metric_columns
        if seg_metrics:
            obs_runtime.inc("fleet.transfers")
            fetched = jax.device_get(seg_metrics)
            metrics_np = jax.tree_util.tree_map(
                lambda *xs: np.concatenate(xs, axis=0), *fetched)
            cols = concat_metrics(saved_cols, metric_columns(metrics_np))
        else:                           # resumed at the final boundary
            cols = dict(saved_cols)
        if "quarantined_count" in cols:
            q_total = int(np.asarray(cols["quarantined_count"]).sum())
            if q_total:
                obs_runtime.event("robustness.quarantine", surface="fleet",
                                  total=q_total, rounds=max_rounds)
        # Tap leaves arrive round-and-lane-stacked (R, B, ...): per-lane
        # demux slices [r][k] like every other metric column.
        tap_cols = {f[len("taps."):]: v for f, v in cols.items()
                    if f.startswith("taps.")} or None
        evals = [[(r, float(v)) for r, v in lane] for lane in evals]
        for r, (attacks, etas_raw, cohorts) in enumerate(round_meta):
            for k, job in enumerate(jobs):
                if r >= job.rounds:
                    continue
                lane_metrics = {"loss": cols["loss"][r][k],
                                "lr": cols["lr"][r][k],
                                "direction_norm":
                                    cols["direction_norm"][r][k]}
                if "kappa_hat" in cols:
                    lane_metrics["kappa_hat"] = cols["kappa_hat"][r][k]
                lane_taps = {f: v[r][k] for f, v in tap_cols.items()} \
                    if tap_cols is not None else None
                hists[k].record(lane_metrics, cohort=cohorts[k],
                                attack=attacks[k], eta=etas_raw[k],
                                m_byz=m_byzs[k], f_round=m_byzs[k],
                                taps=lane_taps)

        out = []
        for k, job in enumerate(jobs):
            lane_state = jax.tree_util.tree_map(
                lambda leaf, kk=k: leaf[kk], state)
            best = max((a for _, a in evals[k]), default=None)
            out.append(FleetResult(label=job.label, job=job,
                                   state=lane_state, history=hists[k],
                                   evals=evals[k], best_eval=best))
        return out


# ---------------------------------------------------------------------------
# Continuous batching: a fixed-capacity bucket stepped chunk-by-chunk, with
# admission / eviction / backfill at segment boundaries.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LaneSlot:
    """Host-side record of one OCCUPIED slot in a continuous bucket.

    ``local`` is the lane's own round clock (0 at admission), decoupled
    from the bucket's global ``rounds_executed`` — all planning (schedule
    resolution, cohort sampling, eval cadence) runs on lane-local rounds,
    so a job admitted mid-run computes exactly what it would have computed
    in a fresh bucket."""
    job: FleetJob
    token: Any                          # caller's opaque handle
    rng: np.random.Generator
    local: int = 0
    hist: FedHistory = dataclasses.field(default_factory=FedHistory)
    evals: list = dataclasses.field(default_factory=list)


class ContinuousBucket:
    """One shape bucket run as a service: B fixed lane slots, stepped one
    scan segment at a time, with jobs entering and leaving at boundaries.

    The compiled program is IDENTICAL to the batch runner's
    (``build_fleet_scan`` of the same bucket key): occupancy is pure
    operand data — empty/finished slots get :func:`lane_filler` operands
    (``active=False`` freezes their state), so admitting, evicting, or
    backfilling a lane never changes the traced shapes.  That is the
    one-compile-per-(bucket x segment-length) invariant, now holding
    under churn.

    Admission writes the new lane's init state into its slot with ONE
    compiled ``dynamic_update_index_in_dim`` over a traced slot index
    (:func:`repro.fleet.lanes.build_lane_admit`) — optionally donating
    the bucket state buffer, so admission updates the resident state in
    place instead of reallocating it.
    """

    def __init__(self, key: tuple, template: FleetJob, capacity: int, *,
                 chunk: Optional[int], fleet_scan: Callable,
                 admit_fn: Callable):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.key = key
        self.capacity = capacity
        self.chunk = chunk
        self._scan = fleet_scan
        self._admit = admit_fn
        self._filler = lane_filler(template)
        filler_state = init_lane_state(template)
        self.state = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * capacity), filler_state)
        self.slots: list[Optional[LaneSlot]] = [None] * capacity
        #: Bucket-global round clock: total scan rounds executed, across
        #: every lane generation this bucket has hosted.
        self.rounds_executed = 0

    # -- occupancy --------------------------------------------------------
    @property
    def occupied(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def free_slot(self) -> Optional[int]:
        for k, s in enumerate(self.slots):
            if s is None:
                return k
        return None

    def slot_of(self, token: Any) -> Optional[int]:
        for k, s in enumerate(self.slots):
            if s is not None and s.token is token:
                return k
        return None

    # -- admission / eviction ---------------------------------------------
    def admit(self, job: FleetJob, token: Any = None, *,
              lane_state: Optional[dict] = None, local: int = 0,
              rng: Optional[np.random.Generator] = None,
              hist: Optional[FedHistory] = None,
              evals: Optional[list] = None,
              slot: Optional[int] = None) -> int:
        """Occupy a free slot with ``job`` (effective at the NEXT segment
        — call only at boundaries, i.e. between :meth:`step` calls).

        The keyword-only arguments re-admit a SURVIVING lane from a
        service snapshot (``FleetService.restore``): mid-run device state,
        local round clock, rng position, history-so-far — the same compiled
        admit program writes it into the slot, so a restored lane is
        indistinguishable from one that never left.
        """
        if slot is not None:
            if self.slots[slot] is not None:
                raise RuntimeError(f"slot {slot} is occupied")
            k = slot
        else:
            k = self.free_slot()
        if k is None:
            raise RuntimeError("bucket is full")
        self.state = self._admit(
            self.state,
            lane_state if lane_state is not None else init_lane_state(job),
            np.int32(k))
        self.slots[k] = LaneSlot(
            job=job, token=token,
            rng=rng if rng is not None else np.random.default_rng(job.seed),
            local=local,
            hist=hist if hist is not None else FedHistory(),
            evals=list(evals) if evals else [])
        obs_runtime.event("fleet.admit", slot=k, label=job.label,
                          at=self.rounds_executed)
        return k

    def cancel(self, k: int) -> FleetResult:
        """Evict a running lane mid-job; returns the PARTIAL result
        (history and evals up to the last completed boundary).  The slot
        is immediately free for backfill; the lane's stale device state
        stays in place, frozen by filler ``active=False`` operands."""
        s = self.slots[k]
        if s is None:
            raise KeyError(f"slot {k} is empty")
        return self._finalize(k, s)

    def _finalize(self, k: int, s: LaneSlot) -> FleetResult:
        self.slots[k] = None
        obs_runtime.event("fleet.evict", slot=k, label=s.job.label,
                          at=self.rounds_executed, rounds=s.local)
        evals = [(r, float(v)) for r, v in s.evals]
        best = max((a for _, a in evals), default=None)
        return FleetResult(label=s.job.label, job=s.job,
                           state=self.lane_state(k), history=s.hist,
                           evals=evals, best_eval=best)

    def lane_state(self, k: int) -> dict:
        return jax.tree_util.tree_map(lambda leaf: leaf[k], self.state)

    # -- stepping ---------------------------------------------------------
    def next_seg_len(self, *, hold_for_pending: bool = False) -> int:
        """Rounds the next segment will scan.

        ``min(max remaining, chunk, every active lane's distance to its
        next eval multiple)`` — for up-front admissions this reproduces
        the batch runner's ``split_segments`` cuts exactly (same traces,
        same carry returns).  With ``hold_for_pending`` the horizon drops
        to ``min(remaining)``: when a job is waiting on this bucket, the
        segment ends the moment the soonest lane can finish, so its slot
        frees at the earliest boundary.
        """
        remaining = [s.job.rounds - s.local
                     for s in self.slots if s is not None]
        if not remaining:
            return 0
        length = min(remaining) if hold_for_pending else max(remaining)
        if self.chunk is not None:
            length = min(length, self.chunk)
        for s in self.slots:
            if (s is not None and s.job.eval_fn is not None
                    and s.job.eval_every):
                length = min(length,
                             s.job.eval_every - s.local % s.job.eval_every)
        return max(int(length), 1)

    def step(self, *, hold_for_pending: bool = False
             ) -> list[tuple[Any, FleetResult]]:
        """Scan ONE segment; returns ``(token, result)`` for every lane
        that finished at this boundary (their slots are already free)."""
        lanes = [(k, s) for k, s in enumerate(self.slots) if s is not None]
        if not lanes:
            return []
        seg = self.next_seg_len(hold_for_pending=hold_for_pending)
        fill_batch, fill_idx, fill_ops = self._filler

        per_round: list[dict] = []
        metas: dict[int, list] = {k: [] for k, _ in lanes}
        for i in range(seg):
            batches, cohorts = [], []
            ops: dict[str, list] = {f: [] for f in _OP_DTYPES}
            for k in range(self.capacity):
                s = self.slots[k]
                if s is None or s.local + i >= s.job.rounds:
                    batch, cohort, lane_ops = fill_batch, fill_idx, fill_ops
                else:
                    batch, cohort, lane_ops, meta = plan_lane_round(
                        s.job, s.local + i, s.rng)
                    metas[k].append((s.local + i,) + meta)
                batches.append(batch)
                cohorts.append(cohort)
                for f in _OP_DTYPES:
                    ops[f].append(lane_ops[f])
            per_round.append(_pack_round(batches, cohorts, ops))
        operands = stack_rounds(per_round)

        start = self.rounds_executed
        with obs_runtime.span("fleet.segment", start=start, end=start + seg,
                              lanes=len(lanes)):
            self.state, metrics = self._scan(self.state, operands)
        self.rounds_executed += seg

        obs_runtime.inc("fleet.transfers")
        fetched = jax.device_get(metrics)
        if "quarantined_count" in fetched:
            q_total = int(np.asarray(fetched["quarantined_count"]).sum())
            if q_total:
                obs_runtime.event("robustness.quarantine",
                                  surface="fleet.service",
                                  total=q_total, rounds=seg)
        tap_cols = fetched["taps"].to_dict() if "taps" in fetched else None
        finished: list[tuple[Any, FleetResult]] = []
        for k, s in lanes:
            for (local_r, attack, eta_raw, cohort) in metas[k]:
                i = local_r - s.local
                lane_metrics = {"loss": fetched["loss"][i][k],
                                "lr": fetched["lr"][i][k],
                                "direction_norm":
                                    fetched["direction_norm"][i][k]}
                if "kappa_hat" in fetched:
                    lane_metrics["kappa_hat"] = fetched["kappa_hat"][i][k]
                lane_taps = {f: v[i][k] for f, v in tap_cols.items()} \
                    if tap_cols is not None else None
                s.hist.record(lane_metrics, cohort=cohort, attack=attack,
                              eta=eta_raw, m_byz=s.job.m_byz,
                              f_round=s.job.m_byz, taps=lane_taps)
            new_local = min(s.local + seg, s.job.rounds)
            if (s.job.eval_fn is not None and s.job.eval_every
                    and new_local != s.local
                    and new_local % s.job.eval_every == 0):
                s.evals.append((new_local,
                                s.job.eval_fn(self.lane_state(k)["params"])))
            s.local = new_local
            if s.local >= s.job.rounds:
                finished.append((s.token, self._finalize(k, s)))
        return finished


def run_fleet(jobs: Sequence[Union[FleetJob, ScenarioSpec]], *,
              max_lanes: Optional[int] = None,
              chunk: Optional[int] = None,
              options: Optional[RoundOptions] = None) -> list[FleetResult]:
    """One-shot convenience: pack, run, return per-lane results."""
    return FleetRunner(jobs, max_lanes=max_lanes, chunk=chunk,
                       options=options).run()
