"""Sharded kernel backend: parity, structure, and detectability under a
REAL (forced 8-device CPU host) multi-device mesh.

The load-bearing acceptance tests (ISSUE 4):

* ``backend="pallas_sharded"`` matches the single-device pallas path and
  the xla oracle per rule x pre — plain coordinate rules (no gram-derived
  mix) BIT-for-bit against solo pallas (per-column math, identical
  kernels per shard; with NNM the psum'd gram is fp-close, not
  bit-identical, so those rows hold to tolerance);
* the jaxpr under the mesh holds ZERO full-width (n, D) dot/sort
  equations (``count_wide_ops == 0``) while xla keeps >= 2;
* non-power-of-two n (17, the paper scale) runs the fused padded-sort
  mixtrim with zero recorded fallbacks;
* the DispatchRecord carries the mesh/device-count resolution, and a
  degraded "pallas_sharded" request is detectable — including through
  ``FleetService.last_dispatch``.

The 8-device half runs in ONE subprocess (jax locks the device count at
first init, and the main pytest process may be on 1 device or — in the
CI ``shard`` job, which sets the XLA_FLAGS at job level — on 8) whose
JSON result is cached module-wide.  Main-process tests below therefore
branch on ``jax.device_count()`` rather than assuming either shape.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys
sys.path.insert(0, os.path.join(%(repo)r, "src"))
import json
import jax, jax.numpy as jnp
import numpy as np

from repro.core import AggregatorSpec
from repro.core import robust as robust_lib
from repro.kernels import dispatch as kd

RULES = ("average", "krum", "multikrum", "gm", "mda",
         "cwtm", "cwmed", "meamed")
PRES = (None, "nnm", "bucketing")

rng = np.random.default_rng(3)
tree = {"w": jnp.asarray(rng.normal(size=(16, 37)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(16, 3, 5)), jnp.float32),
        "s": jnp.asarray(rng.normal(size=(16,)), jnp.float32)}
key = jax.random.PRNGKey(5)

def leaves(t):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(t)]

def spec(rule, pre, backend, f):
    return AggregatorSpec(rule=rule, f=f, pre=pre, bucket_size=2,
                          backend=backend)

out = {"devices": jax.device_count(), "parity": {}, "bit_parity": {},
       "dyn_parity": {}}

for rule in RULES:
    for pre in PRES:
        for f in (0, 3):
            ref = robust_lib.robust_aggregate(tree, spec(rule, pre, "xla", f),
                                              key=key)
            solo = robust_lib.robust_aggregate(
                tree, spec(rule, pre, "pallas", f), key=key)
            got = robust_lib.robust_aggregate(
                tree, spec(rule, pre, "pallas_sharded", f), key=key)
            rec = kd.last_dispatch()
            err_x = max(float(np.abs(a - b).max())
                        for a, b in zip(leaves(got), leaves(ref)))
            err_p = max(float(np.abs(a - b).max())
                        for a, b in zip(leaves(got), leaves(solo)))
            tag = f"{rule}/{pre}/f{f}"
            out["parity"][tag] = {
                "err_vs_xla": err_x, "err_vs_pallas": err_p,
                "mesh_devices": rec.mesh_devices, "mesh_axis": rec.mesh_axis,
                "backend": rec.backend,
                "fallbacks": [d.reason for d in rec.fallbacks]}
            if rule in ("cwtm", "cwmed") and pre is None:
                # pre=None only: with NNM/bucketing the mixing matrix is
                # derived from the gram, and the psum'd sharded gram is
                # fp-close but not bit-identical to the solo blocked gram
                # — a near-tie in distances could flip neighbor selection,
                # so bitwise equality is only GUARANTEED without a
                # gram-derived mix (per-column kernels on identical input).
                out["bit_parity"][tag] = all(
                    np.array_equal(a, b)
                    for a, b in zip(leaves(got), leaves(solo)))

# dynamic-f parity (traced f; the fleet path)
for rule in ("cwtm", "cwmed", "krum", "meamed"):
    for f in (0, 2, 3):
        ref = robust_lib.robust_aggregate_dyn(
            tree, spec(rule, "nnm", "xla", 0), jnp.int32(f))
        got = robust_lib.robust_aggregate_dyn(
            tree, spec(rule, "nnm", "pallas_sharded", 0), jnp.int32(f))
        out["dyn_parity"][f"{rule}/f{f}"] = max(
            float(np.abs(a - b).max())
            for a, b in zip(leaves(got), leaves(ref)))

# lane-batched (vmap over shard_map: sharded fleet buckets)
fs = jnp.asarray([0, 2, 3], jnp.int32)
bt = jax.tree_util.tree_map(
    lambda leaf: jnp.stack([leaf, 2 * leaf, leaf + 1]), tree)
bspec = spec("cwtm", "nnm", "pallas_sharded", 0)
batched = robust_lib.batched_robust_aggregate(bt, bspec, fs)
errs = []
for lane, f in enumerate((0, 2, 3)):
    single = robust_lib.robust_aggregate_dyn(
        jax.tree_util.tree_map(lambda leaf, k=lane: leaf[k], bt),
        bspec, jnp.int32(f))
    lane_out = jax.tree_util.tree_map(lambda leaf, k=lane: leaf[k], batched)
    errs.append(max(float(np.abs(a - b).max())
                    for a, b in zip(leaves(lane_out), leaves(single))))
out["batched_max_err"] = max(errs)

# structural: zero full-width (n, D) wide ops under the mesh
n, d = 16, 8192
wide_tree = {"x": jnp.zeros((n, d), jnp.float32)}
def wide(backend):
    s = AggregatorSpec(rule="cwtm", f=3, pre="nnm", backend=backend)
    return kd.count_wide_ops(
        lambda t: robust_lib.robust_aggregate(t, s), wide_tree, n=n, width=d)
out["wide_ops_sharded"] = wide("pallas_sharded")
out["wide_ops_xla"] = wide("xla")

# non-power-of-two n=17 (PR 1 federated scenarios): fused, zero fallbacks
t17 = {"w": jnp.asarray(rng.normal(size=(17, 300)), jnp.float32)}
got17 = robust_lib.robust_aggregate(
    t17, AggregatorSpec(rule="cwtm", f=4, pre="nnm",
                        backend="pallas_sharded"))
rec17 = kd.last_dispatch()
ref17 = robust_lib.robust_aggregate(
    t17, AggregatorSpec(rule="cwtm", f=4, pre="nnm", backend="xla"))
out["n17_fallbacks"] = [d.reason for d in rec17.fallbacks]
out["n17_padded_noted"] = any("padded to 32" in d.reason
                              for d in rec17.decisions)
out["n17_err"] = max(float(np.abs(a - b).max())
                     for a, b in zip(leaves(got17), leaves(ref17)))

# one minor-swapped 3-D leaf: the flat (D,) aggregate of the sharded
# buffer is row-major and must rebuild the leaf as it is
tsw = {"w": jnp.asarray(rng.normal(size=(8, 200, 50)), jnp.float32)}
sw_spec = AggregatorSpec(rule="cwtm", f=1, pre="nnm",
                         backend="pallas_sharded")
got_sw = robust_lib.robust_aggregate(tsw, sw_spec)
out["swapped_leaf_backend"] = kd.last_dispatch().backend
ref_sw = robust_lib.robust_aggregate(
    tsw, AggregatorSpec(rule="cwtm", f=1, pre="nnm", backend="xla"))
out["swapped_leaf_err"] = max(float(np.abs(a - b).max())
                              for a, b in zip(leaves(got_sw), leaves(ref_sw)))

print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def shard_run():
    """One subprocess drives the whole 8-device matrix; tests share it."""
    script = _SHARD_SCRIPT % {"repo": REPO}
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_backend_parity_all_rules(shard_run):
    assert shard_run["devices"] == 8
    for tag, row in shard_run["parity"].items():
        assert row["backend"] == "pallas_sharded", (tag, row)
        assert row["mesh_devices"] == 8 and row["mesh_axis"] == "shard", row
        assert row["err_vs_xla"] < 1e-4, (tag, row)
        assert row["err_vs_pallas"] < 1e-4, (tag, row)
        # only the documented oracle fallbacks may appear (meamed)
        for reason in row["fallbacks"]:
            assert "meamed" in reason, (tag, row)


@pytest.mark.slow
def test_sharded_coordinate_rules_bit_match_solo_pallas(shard_run):
    """Plain cwtm/cwmed (pre=None) are per-column math on identical input:
    every shard runs the identical fused kernel on its columns, so
    sharding may not change a single bit relative to the single-device
    pallas pipeline.  (NNM rows are excluded: their mixing matrix derives
    from the psum'd gram, which is fp-close but not bit-identical — those
    hold to the 1e-4 tolerance asserted above.)"""
    assert shard_run["bit_parity"], "no coordinate-rule rows collected"
    bad = [t for t, ok in shard_run["bit_parity"].items() if not ok]
    assert not bad, f"sharded != solo pallas bitwise: {bad}"


@pytest.mark.slow
def test_sharded_dyn_and_batched_parity(shard_run):
    for tag, err in shard_run["dyn_parity"].items():
        assert err < 1e-4, (tag, err)
    assert shard_run["batched_max_err"] < 1e-5


@pytest.mark.slow
def test_sharded_jaxpr_has_zero_wide_ops(shard_run):
    """Acceptance: under the mesh the mixed stack exists only as local
    (n, D/k) blocks — no full-width (n, D) dot/sort anywhere."""
    assert shard_run["wide_ops_sharded"] == 0
    assert shard_run["wide_ops_xla"] >= 2


@pytest.mark.slow
def test_sharded_nonpow2_runs_fused_mixtrim(shard_run):
    """n=17 under the sharded backend: padded-sort kernel, zero recorded
    fallbacks (the second documented fallback is gone too)."""
    assert shard_run["n17_fallbacks"] == []
    assert shard_run["n17_padded_noted"]
    assert shard_run["n17_err"] < 1e-4


@pytest.mark.slow
def test_sharded_single_swapped_leaf_matches_xla(shard_run):
    """A tree of one minor-swapped 3-D leaf under the sharded backend: its
    one (D,) aggregate rebuilds the leaf in row-major order."""
    assert shard_run["swapped_leaf_backend"] == "pallas_sharded"
    assert shard_run["swapped_leaf_err"] < 1e-4


# ---------------------------------------------------------------------------
# Main-process (single device): degrade detectability through the fleet
# service — the contract PR 3 established for the other fallbacks.
# ---------------------------------------------------------------------------

def _shard_job():
    from repro.core import AggregatorSpec
    from repro.fed import ClientConfig, FedConfig, constant_attack
    from repro.fleet import FleetJob
    from repro.optim import sgd

    def loss_fn(params, batch):
        return 0.5 * jnp.sum(params["theta"] ** 2), {}

    cfg = FedConfig(n_clients=10, clients_per_round=6, f=2,
                    agg=AggregatorSpec(rule="cwtm", f=2, pre="nnm",
                                       backend="pallas_sharded"),
                    client=ClientConfig(local_steps=0, local_lr=0.05,
                                        algorithm="dshb", beta=0.9))
    return FleetJob(label="shard", cfg=cfg, loss_fn=loss_fn,
                    optimizer=sgd(clip=1.0),
                    params={"theta": jnp.zeros((5,), jnp.float32)},
                    batch_fn=lambda cohort, n_flip, rng:
                        {"idx": np.asarray(cohort)[:, None, None]},
                    rounds=2, schedule=constant_attack("none"))


def test_fleet_service_surfaces_sharded_degrade():
    """A tenant submitting backend="pallas_sharded" on a 1-device host
    must see the degrade on FleetService.last_dispatch: mesh_devices=1
    and a pipeline-level fallback decision — never silent."""
    from repro.serving import FleetService
    if jax.device_count() > 1:
        pytest.skip("degrade only happens on single-device hosts")
    svc = FleetService()
    svc.submit(_shard_job())
    svc.drain()
    rec = svc.last_dispatch
    assert rec is not None, "drain must snapshot a fresh trace's record"
    assert rec.requested == "pallas_sharded" and rec.backend == "xla"
    assert rec.mesh_devices == 1 and rec.mesh_axis is None
    assert any(d.primitive == "pipeline" and d.fell_back
               for d in rec.decisions), rec.describe()


def test_bucket_key_includes_mesh_signature():
    """The compiled fleet round bakes the mesh-routing decision in, so the
    bucket key must change when the mesh does (compile-cache hygiene)."""
    from repro.fleet import bucket_key
    from repro.fleet.runner import _mesh_sig
    from repro.launch.mesh import make_debug_mesh
    job = _shard_job()
    base = bucket_key(job)
    assert _mesh_sig() in base
    if jax.device_count() >= 4:
        with jax.set_mesh(make_debug_mesh(2, 2)):
            assert bucket_key(job) != base
    else:
        # single-device host: the signature is the bare device count
        assert _mesh_sig() == (jax.device_count(),)


def test_aggregation_mesh_axis_preference():
    """Axis plumbing: the sharded backend prefers the model axis of an
    active mesh, and builds the ad-hoc 1-D mesh only with >1 devices."""
    from repro.launch.mesh import aggregation_axis, aggregation_mesh
    devs = np.asarray(jax.devices()[:1])
    one = jax.sharding.Mesh(devs.reshape(1, 1), ("data", "model"))
    assert aggregation_axis(one) is None
    if jax.device_count() == 1:
        assert aggregation_mesh() is None
