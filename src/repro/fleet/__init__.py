"""Multi-tenant fleet engine: B federated scenarios per jitted round.

Division of labor with the rest of the repo:

* ``repro.fed`` — ONE scenario per process; the reference orchestration
  (its full-participation round is bit-for-bit a trainer step).
* ``repro.fleet`` — MANY scenarios per device: jobs are packed into shape
  buckets, their states stacked along a leading lane axis, and a single
  vmapped round steps the whole bucket.  Per-lane (f, attack family, eta,
  beta, local_lr, server lr) are traced operands — one compile per shape
  bucket, not per job — via a traced f into ``repro.core.robust`` and
  ``repro.core.attacks.apply_attack_dyn``.

A B=1 fleet is the sequential per-job loop; a lane inside a B-lane bucket
produces bit-for-bit the same trajectory (tested), so batching is purely a
throughput lever — `benchmarks/bench_fleet.py` measures it.
"""
from repro.fleet.lanes import (
    LANE_OP_FIELDS, build_fleet_round, build_fleet_scan, build_lane_admit,
    build_lane_round, donation_supported,
)
from repro.fleet.runner import (
    ContinuousBucket, FleetJob, FleetResult, FleetRunner, LaneBucket,
    LaneSlot, SCENARIO_OPTIMIZER, ScenarioSpec, apply_job_options,
    bucket_key, init_lane_state, job_from_spec, lane_filler,
    plan_lane_round, run_fleet,
)

__all__ = [
    "LANE_OP_FIELDS", "build_fleet_round", "build_fleet_scan",
    "build_lane_admit", "build_lane_round", "donation_supported",
    "ContinuousBucket", "FleetJob", "FleetResult", "FleetRunner",
    "LaneBucket", "LaneSlot", "SCENARIO_OPTIMIZER", "ScenarioSpec",
    "apply_job_options", "bucket_key", "init_lane_state", "job_from_spec",
    "lane_filler", "plan_lane_round", "run_fleet",
]
