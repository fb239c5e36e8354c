"""Production mesh construction + per-arch mesh-axes resolution.

Defined as FUNCTIONS so importing this module never touches jax device
state (required: the dry-run sets XLA_FLAGS before any jax init).
"""
from __future__ import annotations

from typing import Any, Optional

import jax

from repro.configs.base import ModelConfig
from repro.models.common import MeshAxes, pad_heads

#: TPU v5e hardware constants for the roofline (see system assignment).
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

MODEL_PAR = 16
DATA_PAR = 16
PODS = 2


def current_mesh():
    """The (abstract) mesh of the innermost ``jax.set_mesh`` scope, or None.

    Abstract, so it can be read while tracing; the aggregation shard_maps
    and axis-size lookups need nothing more."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def as_shardings(specs: Any, mesh: Optional[jax.sharding.Mesh] = None) -> Any:
    """Pytree of PartitionSpec -> NamedSharding over ``mesh`` (defaults to
    the concrete mesh of the active ``jax.set_mesh`` scope).  Existing
    Sharding leaves pass through."""
    if mesh is None:
        mesh = jax.sharding.get_mesh()
    if mesh.empty:
        raise ValueError("as_shardings needs a mesh or an active "
                         "jax.set_mesh()")

    def conv(s):
        if isinstance(s, jax.sharding.Sharding):
            return s
        return jax.sharding.NamedSharding(mesh, s)

    return jax.tree_util.tree_map(
        conv, specs,
        is_leaf=lambda s: isinstance(
            s, (jax.sharding.PartitionSpec, jax.sharding.Sharding)))


#: Mesh axes the sharded aggregation backend prefers to shard the
#: flattened (n, D) feature dim over, in order.  "model" is where the
#: parameters (and so the per-worker gradients) already live on the
#: production mesh; "shard" is the ad-hoc 1-D mesh name below.
AGG_AXIS_PREFERENCE = ("model", "shard")


def aggregation_axis(mesh) -> Optional[str]:
    """The mesh axis the aggregation stage shards D over, or None.

    Prefers the axes in :data:`AGG_AXIS_PREFERENCE` (size > 1), else the
    largest axis; None when every axis has size 1 (nothing to shard)."""
    sizes = dict(mesh.shape)
    for name in AGG_AXIS_PREFERENCE:
        if sizes.get(name, 1) > 1:
            return name
    if not sizes:
        return None
    name = max(sizes, key=lambda a: sizes[a])
    return name if sizes[name] > 1 else None


def aggregation_mesh() -> Optional[tuple[jax.sharding.Mesh, str]]:
    """(mesh, axis) the ``pallas_sharded`` backend should run over, or None.

    The innermost ``jax.set_mesh`` scope wins (sharding D along its
    :func:`aggregation_axis`); with no active mesh, a host with more than
    one visible device gets an ad-hoc 1-D mesh over all of them.  None
    means "no multi-device mesh" — the dispatcher records the degrade to
    the leaf-streamed XLA path (never silent)."""
    import numpy as np
    mesh = current_mesh()
    if mesh is not None:
        ax = aggregation_axis(mesh)
        return (mesh, ax) if ax is not None else None
    if jax.device_count() > 1:
        return jax.sharding.Mesh(np.asarray(jax.devices()), ("shard",)), \
            "shard"
    return None


#: Mesh axes the HIERARCHICAL aggregation backend ("pallas_hier") prefers
#: to shard the worker dim n over, in order.  "workers" is the dedicated
#: axis of :func:`make_hier_mesh`; "data" is where per-worker gradients
#: already live on the production mesh; "pod" covers multi-pod layouts.
AGG_WORKER_AXIS_PREFERENCE = ("workers", "data", "pod")


def aggregation_worker_axis(mesh,
                            model_axis: Optional[str]) -> Optional[str]:
    """The mesh axis hierarchical aggregation shards the worker dim over,
    or None (1-D hier: D-sharded only).

    Prefers :data:`AGG_WORKER_AXIS_PREFERENCE` (size > 1, distinct from the
    D axis), else the largest remaining axis."""
    sizes = dict(mesh.shape)
    for name in AGG_WORKER_AXIS_PREFERENCE:
        if name != model_axis and sizes.get(name, 1) > 1:
            return name
    rest = {a: k for a, k in sizes.items() if a != model_axis and k > 1}
    if not rest:
        return None
    return max(rest, key=lambda a: rest[a])


def hier_aggregation_mesh() -> Optional[
        tuple[jax.sharding.Mesh, Optional[str], str]]:
    """(mesh, worker_axis | None, model_axis) for ``backend="pallas_hier"``,
    or None when the host has no multi-device mesh.

    The innermost ``jax.set_mesh`` scope wins: D shards along its
    :func:`aggregation_axis` and the worker dim along
    :func:`aggregation_worker_axis` (None on 1-D meshes — the stack stays
    worker-replicated and only D shards).  With no active mesh, >= 4
    visible devices (even count) get an ad-hoc 2-D (2, k/2)
    ("workers", "shard") mesh; 2..3 devices get the 1-D "shard" mesh.
    None means "no multi-device mesh" — the dispatcher records the degrade
    to the dense bucketing path (never silent)."""
    import numpy as np
    mesh = current_mesh()
    if mesh is not None:
        model_ax = aggregation_axis(mesh)
        if model_ax is None:
            return None
        return mesh, aggregation_worker_axis(mesh, model_ax), model_ax
    dc = jax.device_count()
    if dc >= 4 and dc % 2 == 0:
        devs = np.asarray(jax.devices()).reshape(2, dc // 2)
        return jax.sharding.Mesh(devs, ("workers", "shard")), "workers", \
            "shard"
    if dc > 1:
        return jax.sharding.Mesh(np.asarray(jax.devices()), ("shard",)), \
            None, "shard"
    return None


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes: sharding constraints inside the
    model (``models.common.constrain``) then guide the partitioner, where
    the Explicit axes ``jax.make_mesh`` defaults to would make them
    assertions."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_hier_mesh(workers: int, model: int):
    """2-D mesh for hierarchical aggregation: the (n, D) stack lives
    sharded along BOTH axes (worker shards x D shards)."""
    return make_mesh((workers, model), ("workers", "model"))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (PODS, DATA_PAR, MODEL_PAR) if multi_pod else (DATA_PAR, MODEL_PAR)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU integration tests (host-device override)."""
    return make_mesh((data, model), ("data", "model"))


def mesh_axes_for(cfg: ModelConfig, *, multi_pod: bool = False,
                  model_par: int = MODEL_PAR,
                  data_axes: tuple[str, ...] | None = None,
                  pad_kv: bool = False) -> MeshAxes:
    """Resolve per-arch sharding switches for a mesh geometry."""
    if data_axes is None:
        data_axes = ("pod", "data") if multi_pod else ("data",)
    _, hkv_p, _, shard_kv = pad_heads(cfg.num_heads, cfg.num_kv_heads,
                                      model_par, pad_kv=pad_kv)
    shard_expert = cfg.num_experts > 0 and cfg.num_experts % model_par == 0
    return MeshAxes(data=tuple(data_axes), model="model", model_par=model_par,
                    shard_kv=shard_kv, shard_expert=shard_expert,
                    pad_kv_to_mesh=pad_kv)


def n_workers(*, multi_pod: bool = False) -> int:
    return DATA_PAR * (PODS if multi_pod else 1)
