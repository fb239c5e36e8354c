"""Kernel backend layer: route the aggregation hot path to Pallas or XLA.

``repro.core.robust`` is backend-polymorphic: every aggregation pipeline
declares ``AggregatorSpec.backend`` ("xla" | "pallas" | "pallas_sharded" |
"auto") and this module turns that request into concrete kernel calls over
views of the worker-stacked pytree:

* **flatten** — :func:`flatten_worker_stack` concatenates every leaf's
  ``(n, ...)`` stack into a single ``(n, D)`` buffer plus static
  leaf-segment metadata, for the mesh backends that shard one buffer
  along D; on a single device :func:`split_worker_stack` instead hands
  the kernels each leaf as the 3-D leaf view ``(n, R, C)`` (C the minor
  dim of the leaf's TPU layout, R the product of the others but n), which
  the kernels read in the leaf's own tiled layout, so the stack is
  neither copied nor relaid out (at SmolLM-360M width with n=4 the stack
  is 5.8 GB);
* **gram** — the blocked Pallas kernel (``kernels/gram``), one wide
  (n, W) tile or (n, r, w) leaf block per grid step accumulating the
  tiny (n, n) Gram matrix (the block from :func:`pick_block_d` or
  ``tiling.pick_leaf_block``, recorded on each decision with its view);
* **combine** — the streamed coefficient kernel (``kernels/combine``)
  applying the gram-rule weights without re-materializing anything;
* **mixtrim** — the fused NNM-mix + coordinate trim/median kernel
  (``kernels/mixtrim``), a static f compiled in or a traced f as a rank
  mask, so the mixed stack ``Y = M @ X`` never exists in HBM (any n: a
  leaf view sorts its workers' slabs elementwise; the flat form's bitonic
  sort pads to the next power of two with sentinel rows).

Under a multi-device mesh, ``backend="pallas_sharded"`` runs the same
pipeline shard_map'd along D (:mod:`repro.kernels.shard`): per-shard
blocked gram + an O(n^2)-byte psum, replicated coefficient math,
shard-local combine/mixtrim — the memory bound per device drops from
n x largest-leaf-shard to the (n, W) VMEM tile.

``backend="pallas_hier"`` is the hierarchical form for large worker
counts (``AggregatorSpec.hier``): the fused bucketed-gram kernel
(``kernels/bucketgram``) reduces the (n, D) stack to ceil(n/s) bucket
means + their reduced Gram in one pass — on a (possibly 2-D workers x
model) mesh the stack lives sharded along BOTH n and D, and only
REDUCED-population collectives cross shards (:func:`resolve_hier_mesh` /
``shard.sharded_bucketgram``).  The downstream NNM/coeff/mixtrim
primitives then run on the (n/s)-row stack through the same dispatchers
("pallas_hier" routes them like "pallas_sharded" over the model axis).

Every dispatch decision — including jnp-oracle fallbacks (meamed, sketch
grams) and a "pallas_sharded" request degrading to the leaf-streamed XLA
path because no multi-device mesh exists — is recorded on a
:class:`DispatchRecord` (with its ``mesh_devices`` / ``mesh_axis``
resolution) kept in a bounded ring — :func:`dispatch_history` for the
trail, :func:`last_dispatch` for the head, both re-exported through
``repro.obs.runtime`` — so a requested kernel path that quietly ran XLA
is detectable, and not just for the very last dispatch.

Decisions are **static** per (spec, shapes): they are taken while tracing,
so under ``jax.jit`` the record reflects the most recent TRACE, not the
most recent execution (a jit cache hit re-runs the compiled kernel without
re-recording).  That is the faithful semantics: the backend choice is
baked into the compiled executable.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

try:        # jaxpr types moved out of jax.core on newer jax releases
    from jax.extend import core as _jaxpr_core
    _ = (_jaxpr_core.ClosedJaxpr, _jaxpr_core.Jaxpr)
except (ImportError, AttributeError):       # pragma: no cover - old jax
    from jax import core as _jaxpr_core

from repro.kernels import shard as shardlib
from repro.kernels.combine import combine as _combine_op
from repro.kernels.gram import gram as _gram_op
from repro.kernels.gram import gram_batched as _gram_batched_op
from repro.kernels.mixtrim import mixtrim as _mixtrim_op
from repro.kernels.mixtrim.ref import coordinate_rule
from repro.kernels.target import on_tpu, resolve_interpret
from repro.kernels.tiling import (  # noqa: F401  (pick_block_d: re-export)
    block_width, grid_steps, leaf_block, leaf_grid, leaf_shape,
    leaf_view_fits, minor_swapped, pick_block_d, sort_height,
)

Array = jax.Array
PyTree = Any

BACKENDS = ("xla", "pallas", "pallas_sharded", "pallas_hier", "auto")

#: Backends that run the Pallas kernel pipeline (the remaining value a
#: KernelDecision.requested can hold is "xla").
_PALLAS_BACKENDS = ("pallas", "pallas_sharded", "pallas_hier")

#: Backends whose downstream primitives run the shard_map'd kernel forms.
_SHARDED_BACKENDS = ("pallas_sharded", "pallas_hier")


def resolve_backend(requested: str, *, hier: bool = False) -> str:
    """Resolve "auto" to a concrete backend.

    "auto" on TPU picks "pallas" on a single device and "pallas_sharded"
    on multi-device hosts (the shard_map'd pipeline: per-shard blocked
    gram + psum, shard-local combine/mixtrim — see kernels/shard.py), so
    the deployment shapes that matter most no longer pay the two
    full-width (n, d) HBM intermediates of the leaf-streamed path; with
    ``hier=True`` (a hierarchical spec) the multi-device pick is
    "pallas_hier" instead, so the bucketed reduction runs sharded too.
    Off-TPU "auto" stays "xla" (interpret-mode kernels are a structural
    tool, not a fast path).  Explicit requests are always honored —
    "pallas_sharded" / "pallas_hier" additionally need a multi-device mesh
    at dispatch time (:func:`resolve_shard_mesh` /
    :func:`resolve_hier_mesh`); without one they degrade (to the
    leaf-streamed XLA pipeline / the dense bucketing path) and the degrade
    is RECORDED, never silent.
    """
    if requested not in BACKENDS:
        raise ValueError(
            f"unknown backend {requested!r}; expected one of {BACKENDS}")
    if requested == "auto":
        if on_tpu():
            if jax.device_count() == 1:
                return "pallas"
            return "pallas_hier" if hier else "pallas_sharded"
        return "xla"
    return requested


def resolve_shard_mesh() -> Optional[tuple[jax.sharding.Mesh, str]]:
    """(mesh, axis) for the sharded backend, or None when the host has no
    multi-device mesh to shard over (lazy import: the kernels package must
    stay importable without touching jax device state)."""
    from repro.launch.mesh import aggregation_mesh
    return aggregation_mesh()


def resolve_hier_mesh() -> Optional[
        tuple[jax.sharding.Mesh, Optional[str], str]]:
    """(mesh, worker_axis | None, model_axis) for the hierarchical backend,
    or None when the host has no multi-device mesh (worker_axis is None on
    1-D meshes: D-sharded hier)."""
    from repro.launch.mesh import hier_aggregation_mesh
    return hier_aggregation_mesh()


# ---------------------------------------------------------------------------
# Decision record.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class KernelDecision:
    """One primitive-level routing decision."""
    #: "gram" | "combine" | "mixtrim" | "meamed" | "pipeline", plus
    #: "autogm_coeff": AutoGM's adaptive-weight solve has no kernel form,
    #: so pallas-backed autogm pipelines always carry an explicit xla
    #: decision for it (gram/combine still run the kernels).
    primitive: str
    requested: str          # backend asked for at this call site
    used: str               # "pallas[-sharded][-interpret]" | "xla"
    reason: str = ""        # why `used` differs from the pallas kernel path
    #: Coordinates of each worker one grid step streams (per shard under
    #: the sharded backends): the tile width W of a flat view, r x w of a
    #: leaf view's (r, w) block; its number of grid steps; None off-kernel.
    block_d: Optional[int] = None
    grid_steps: Optional[int] = None
    #: Operand view: "leaf" (a (n, R, C) leaf in its own layout) or
    #: "flat" (a (n, D) stack or shard); None off-kernel.
    view: Optional[str] = None

    @property
    def fell_back(self) -> bool:
        return self.requested in _PALLAS_BACKENDS and self.used == "xla"


@dataclasses.dataclass
class DispatchRecord:
    """The decision trail of one ``robust_aggregate`` dispatch."""
    requested: str          # AggregatorSpec.backend as given ("auto" kept)
    backend: str            # resolved backend
    rule: str
    pre: Optional[str]
    #: Whether f was traced (a fleet lane's budget) rather than a python
    #: int; set by ``robust_aggregate`` after it opens the record.
    dyn: bool = False
    #: Mesh decision for the sharded backends: how many devices the
    #: aggregation actually sharded over (1 = unsharded — a
    #: "pallas_sharded"/"pallas_hier" record with mesh_devices=1 is a
    #: DEGRADED request, paired with a recorded "pipeline" fallback
    #: decision) and along which mesh axis the feature dim was split.
    mesh_devices: int = 1
    mesh_axis: Optional[str] = None
    #: Hierarchical stage: whether this dispatch ran a bucketed
    #: pre-reduction, its resolved bucket size (None = the shape-level
    #: floor(n/2f) default, resolved at flatten time), and — on the 2-D
    #: mesh form — the mesh axis the WORKER dim sharded over (None: the
    #: stack stayed worker-replicated, D-sharded only).
    hier: bool = False
    bucket_size: Optional[int] = None
    mesh_worker_axis: Optional[str] = None
    decisions: list = dataclasses.field(default_factory=list)

    @property
    def fallbacks(self) -> list:
        """Decisions where a requested Pallas kernel silently ran as XLA."""
        return [d for d in self.decisions if d.fell_back]

    def describe(self) -> str:
        mesh = f" mesh={self.mesh_devices}x{self.mesh_axis}" \
            if self.mesh_axis else ""
        if self.mesh_worker_axis:
            mesh += f" workers={self.mesh_worker_axis}"
        hier = f" hier(s={self.bucket_size or 'auto'})" if self.hier else ""
        parts = [f"{self.requested}->{self.backend} rule={self.rule} "
                 f"pre={self.pre or 'none'} dyn={self.dyn}{hier}{mesh}"]
        for d in self.decisions:
            why = f" ({d.reason})" if d.reason else ""
            tile = (f" {VIEWS[d.view]} W={d.block_d} steps={d.grid_steps}"
                    if d.block_d is not None else "")
            parts.append(f"  {d.primitive}: {d.used}{tile}{why}")
        return "\n".join(parts)


#: Bounded dispatch-record ring (most recent DISPATCH_HISTORY_LIMIT
#: traces).  Queryable here and re-exported through ``repro.obs.runtime``.
DISPATCH_HISTORY_LIMIT = 256

_HISTORY: deque = deque(maxlen=DISPATCH_HISTORY_LIMIT)
_OPENED = 0                 # lifetime records opened (the ring may drop)


def last_dispatch() -> Optional[DispatchRecord]:
    """The most recently OPENED dispatch record — the head of the ring
    (trace-time semantics — see module docstring).  None until the first
    backend-routed aggregation."""
    return _HISTORY[-1] if _HISTORY else None


def dispatch_history(limit: Optional[int] = None) -> list:
    """The most recent dispatch records, oldest first (bounded by
    :data:`DISPATCH_HISTORY_LIMIT`); ``limit`` keeps only the newest N."""
    records = list(_HISTORY)
    if limit is not None:
        records = records[-limit:]
    return records


def dispatch_count() -> int:
    """Monotone count of records ever opened in this process — lets callers
    detect "a new trace happened" without relying on ring identity (the
    bounded ring makes length-based checks unreliable)."""
    return _OPENED


def open_record(*, requested: str, backend: str, rule: str,
                pre: Optional[str], mesh_devices: int = 1,
                mesh_axis: Optional[str] = None,
                hier: bool = False,
                bucket_size: Optional[int] = None,
                mesh_worker_axis: Optional[str] = None) -> DispatchRecord:
    """Start a fresh decision record; subsequent primitive dispatches in
    this trace append to it."""
    global _OPENED
    rec = DispatchRecord(requested=requested, backend=backend, rule=rule,
                         pre=pre, mesh_devices=mesh_devices,
                         mesh_axis=mesh_axis, hier=hier,
                         bucket_size=bucket_size,
                         mesh_worker_axis=mesh_worker_axis)
    _HISTORY.append(rec)
    _OPENED += 1
    # Mirror into the runtime event ring (lazy import: obs.runtime imports
    # this module at its tail, so the dependency must stay one-way here).
    # The args hold the LIVE record — decisions appended later in this
    # trace are visible at export time (sanitization is lazy).
    from repro.obs import runtime as _runtime
    _runtime.event("kernels.dispatch", record=rec)
    return rec


def record_decision(primitive: str, requested: str, used: str,
                    reason: str = "", tile: Optional[tuple] = None) -> None:
    """Append a decision to the open record (once: the per-leaf calls of a
    split stack repeat the same decision for leaves of one tile).
    ``tile`` is the kernel's (coordinates per grid step, grid steps,
    view) from :func:`_tile`."""
    if _HISTORY:
        dec = KernelDecision(primitive, requested, used, reason,
                             *(tile or (None, None, None)))
        if dec not in _HISTORY[-1].decisions:
            _HISTORY[-1].decisions.append(dec)


def _pallas_used(interpret: bool, sharded: bool = False) -> tuple[str, str]:
    base = "pallas-sharded" if sharded else "pallas"
    if interpret:
        return base + "-interpret", "no TPU: kernel body runs interpreted"
    return base, ""


#: How ``describe()`` prints a decision's operand view.
VIEWS = {"leaf": "leaf (n,R,C)", "flat": "flat (n,D)"}


def _tile(x, block_d: Optional[int], mesh=None,
          axis: Optional[str] = None) -> tuple[int, int, str]:
    """(coordinates per grid step, grid steps, view) a kernel streams the
    stack ``x`` (an array or its shape and dtype) with: a (n, R, C) leaf
    view in (r, w) blocks, else the (n, d) stack in ``block_d``-wide
    tiles, the widest that fits VMEM if None; under a mesh, for one
    shard's columns."""
    if len(x.shape) == 3:
        r, w = leaf_block(x.shape, block_d, x.dtype)
        rs, ws = leaf_grid(x.shape, (r, w))
        return r * w, rs * ws, "leaf"
    n, d = x.shape
    if mesh is not None:
        d = shardlib.local_width(d, mesh, axis)
    w = block_width(d, block_d, n, x.dtype)
    return w, grid_steps(d, w), "flat"


def _block_arg(x: Array, block_d: Optional[int], tile: tuple):
    """The ``block_d`` a kernel wrapper takes for ``x``: the flat tile
    width just picked, or for a leaf view the rows as given (the wrapper
    picks the same block again)."""
    return tile[0] if x.ndim == 2 else block_d


def _pad_note(n: int, view: str) -> str:
    """Observability note for the sentinel-padded bitonic sort (the flat
    view's; a leaf view sorts slabs and pads nothing)."""
    if view == "leaf" or sort_height(n) == n:
        return ""
    return f"n={n} padded to {sort_height(n)} with sort sentinels"


# ---------------------------------------------------------------------------
# Flatten / unflatten: one contiguous (n, D) view of the worker stack.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StackLayout:
    """Static leaf-segment metadata of a flattened worker stack."""
    treedef: Any
    segments: tuple         # of (offset, size, trailing_shape)
    n: int                  # worker count
    width: int              # total feature width D


def _stack_layout(tree: PyTree) -> tuple[list, StackLayout]:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    segs, off = [], 0
    for leaf in leaves:
        size = int(np.prod(leaf.shape[1:], dtype=np.int64))
        segs.append((off, size, tuple(leaf.shape[1:])))
        off += size
    return leaves, StackLayout(treedef, tuple(segs), leaves[0].shape[0], off)


def leaf_view_of(leaf: Array) -> Array:
    """A worker-stacked leaf as its (n, R, C) leaf view
    (``tiling.leaf_shape``): its last two dims swapped first where the
    TPU holds it minor-swapped, so the view keeps its memory order."""
    if minor_swapped(leaf.shape):
        leaf = jnp.swapaxes(leaf, -1, -2)
    return jnp.reshape(leaf, leaf_shape(leaf.shape))


def leaf_of_view(view: Array, shape: tuple) -> Array:
    """Undo :func:`leaf_view_of`: ``view`` is the (n, R, C) leaf view of
    a leaf of ``shape``, or its (R, C) aggregate (the worker axis reduced
    away)."""
    out = shape if view.ndim == 3 else shape[1:]
    if not minor_swapped(shape):
        return view.reshape(out)
    return jnp.swapaxes(view.reshape((*out[:-2], out[-1], out[-2])), -1, -2)


def split_worker_stack(tree: PyTree) -> tuple[list, StackLayout]:
    """Every leaf's 3-D leaf view ``(n, R, C)`` (:func:`leaf_view_of`)
    plus the same layout :func:`flatten_worker_stack` gives, for kernels
    that run leaf by leaf.  The view keeps the order in which the TPU's
    tiled layout holds the leaf, so it is a bitcast, no copy and no
    relayout, when the dim above its minor one is a multiple of the
    sublane tile (8 rows of fp32), as in every matrix leaf of the
    benchmark's models; a 2-D leaf (n, C) becomes (n, 1, C), a small
    relayout.  A stack of more workers than ``tiling.leaf_view_fits``
    allows goes as flat (n, d_i) views instead (a relayout on a TPU)."""
    leaves, layout = _stack_layout(tree)
    return [leaf_view_of(leaf) if leaf_view_fits(layout.n, leaf.dtype)
            else jnp.reshape(leaf, (layout.n, -1)) for leaf in leaves], layout


def flatten_worker_stack(tree: PyTree) -> tuple[Array, StackLayout]:
    """Concatenate a worker-stacked pytree into one contiguous (n, D) view.

    Every leaf carries a leading worker axis n; the result is a single
    buffer the kernels can stream without per-leaf dispatch.  Mixed leaf
    dtypes promote under concatenation (uniform fp32 / bf16 stacks — the
    only cases the pipeline produces — keep their dtype)."""
    leaves, layout = _stack_layout(tree)
    return flat_stack(leaves), layout


def flat_stack(segs: list, layout: Optional[StackLayout] = None) -> Array:
    """One (n, D) buffer of worker-stacked leaves in row-major order, or
    of their split views (``layout`` given: each leaf view is put back in
    its leaf's order first): a copy unless there is one 2-D segment."""
    if layout is not None:
        segs = [leaf_of_view(v, (layout.n, *shape)) if v.ndim == 3 else v
                for v, (_, _, shape) in zip(segs, layout.segments)]
    flats = [v.reshape(v.shape[0], -1) for v in segs]
    return flats[0] if len(flats) == 1 else jnp.concatenate(flats, axis=1)


def unflatten_aggregate(vecs, layout: StackLayout) -> PyTree:
    """Rebuild the aggregated pytree (worker axis removed) from the (D,)
    aggregate of a flattened stack, or from the list of per-leaf
    aggregates of a split one: (R, C) of a leaf view, (d_i,) of a flat
    view.  The rank tells a leaf view's aggregate from a row-major one,
    even in a tree of one leaf."""
    vecs = list(vecs) if isinstance(vecs, (list, tuple)) else [vecs]
    if len(vecs) == 1 and vecs[0].ndim == 1:
        vec = vecs[0]
        leaves = [jax.lax.slice_in_dim(vec, off, off + size,
                                       axis=0).reshape(shape)
                  for off, size, shape in layout.segments]
    else:
        leaves = [leaf_of_view(v, (layout.n, *shape)) if v.ndim == 2
                  else v.reshape(shape)
                  for v, (_, _, shape) in zip(vecs, layout.segments)]
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


# ---------------------------------------------------------------------------
# Primitive dispatchers.
# ---------------------------------------------------------------------------

def count_wide_ops(fn, *example_args, n: int, width: int,
                   primitives: tuple = ("dot_general", "sort")) -> int:
    """Structural fusion check: count ``primitives`` equations anywhere in
    ``fn``'s jaxpr producing a full-width (n, width) value.

    With the default primitives that shape signature is exactly the
    materialized NNM-mixed stack (the ``Y = M @ X`` dot and the full-width
    sort): the XLA coordinate path has them, the fused mixtrim path must
    not — its Pallas kernel jaxpr only ever holds (n, W) tiles.  Used
    by ``benchmarks/bench_agg_cost.py`` and the perf gate to keep the
    elimination from regressing; ``("concatenate",)`` finds a flattened
    copy of the whole stack.
    """
    closed = jax.make_jaxpr(fn)(*example_args)

    def sub_jaxprs(params):
        for v in params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for u in vs:
                if isinstance(u, _jaxpr_core.ClosedJaxpr):
                    yield u.jaxpr
                elif isinstance(u, _jaxpr_core.Jaxpr):
                    yield u

    def count(jaxpr) -> int:
        c = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in primitives:
                for var in eqn.outvars:
                    if tuple(getattr(var.aval, "shape", ())) == (n, width):
                        c += 1
            for sub in sub_jaxprs(eqn.params):
                c += count(sub)
        return c

    return count(closed.jaxpr)


def dispatch_gram(x: Array, *, backend: str, block_d: Optional[int] = None,
                  mesh: Optional[jax.sharding.Mesh] = None,
                  axis: Optional[str] = None) -> Array:
    """(n, D) or a (n, R, C) leaf view -> (n, n) fp32 Gram matrix through
    the chosen backend.

    ``backend="pallas_sharded"`` needs the resolved (mesh, axis): the
    blocked kernel runs per D-shard and the tiny partial Grams psum."""
    if backend in _SHARDED_BACKENDS:
        interpret = resolve_interpret()
        used, why = _pallas_used(interpret, sharded=True)
        tile = _tile(x, block_d, mesh, axis)
        record_decision("gram", backend, used, why, tile)
        return shardlib.sharded_gram(x, mesh=mesh, axis=axis,
                                     block_d=tile[0], interpret=interpret)
    if backend == "pallas":
        interpret = resolve_interpret()
        used, why = _pallas_used(interpret)
        tile = _tile(x, block_d)
        record_decision("gram", "pallas", used, why, tile)
        return _gram_op(x, block_d=_block_arg(x, block_d, tile),
                        interpret=interpret)
    record_decision("gram", backend, "xla")
    return _gram_op(x, use_pallas=False)


def dispatch_gram_batched(x: Array, *, backend: str,
                          block_d: Optional[int] = None) -> Array:
    """(B, n, D) -> (B, n, n): the lane-batched Gram pass, one launch for a
    whole fleet shape bucket (grid = lanes x d-blocks)."""
    if backend == "pallas":
        interpret = resolve_interpret()
        used, why = _pallas_used(interpret)
        tile = _tile(jax.ShapeDtypeStruct(x.shape[1:], x.dtype), block_d)
        record_decision("gram_batched", "pallas", used, why, tile)
        return _gram_batched_op(x, block_d=tile[0], interpret=interpret)
    record_decision("gram_batched", backend, "xla")
    return _gram_batched_op(x, use_pallas=False)


def dispatch_bucketgram(x: Array, bmat: Array, *, backend: str,
                        with_gram: bool = True,
                        block_n: Optional[int] = None,
                        block_d: Optional[int] = None,
                        mesh: Optional[jax.sharding.Mesh] = None,
                        worker_axis: Optional[str] = None,
                        axis: Optional[str] = None
                        ) -> tuple[Array, Optional[Array]]:
    """(n, D) stack + (n_b, n) assignment -> (bucket means (n_b, D) in the
    stack dtype, reduced (n_b, n_b) fp32 Gram | None) — the hierarchical
    pre-reduction, fused so neither the permuted nor the reduced stack
    materializes in HBM.

    ``backend="pallas_hier"`` needs the resolved (mesh, worker_axis, axis):
    the stack shards along workers x D and only reduced-population psums
    cross shards.  "pallas_sharded" runs the 1-D D-sharded form over its
    (mesh, axis).  "pallas" is the single-device fused kernel; anything
    else runs the jnp oracle (RECORDED)."""
    from repro.kernels.bucketgram import bucket_means_gram as _bucketgram_op
    if backend == "pallas_hier":
        interpret = resolve_interpret()
        used, why = _pallas_used(interpret, sharded=True)
        w = f"workers={worker_axis}" if worker_axis else "D-sharded only"
        record_decision("bucketgram", backend, used,
                        f"{why}; {w}" if why else w)
        return shardlib.sharded_bucketgram(
            x, bmat, mesh=mesh, worker_axis=worker_axis, model_axis=axis,
            with_gram=with_gram, block_n=block_n, block_d=block_d,
            interpret=interpret)
    if backend == "pallas_sharded":
        interpret = resolve_interpret()
        used, why = _pallas_used(interpret, sharded=True)
        record_decision("bucketgram", backend, used, why)
        return shardlib.sharded_bucketgram(
            x, bmat, mesh=mesh, worker_axis=None, model_axis=axis,
            with_gram=with_gram, block_n=block_n, block_d=block_d,
            interpret=interpret)
    if backend == "pallas":
        interpret = resolve_interpret()
        used, why = _pallas_used(interpret)
        record_decision("bucketgram", "pallas", used, why)
        return _bucketgram_op(x, bmat, with_gram=with_gram, block_n=block_n,
                              block_d=block_d, interpret=interpret)
    record_decision("bucketgram", backend, "xla")
    return _bucketgram_op(x, bmat, with_gram=with_gram, use_pallas=False)


def dispatch_combine(x: Array, coeff: Array, *, backend: str,
                     block_d: Optional[int] = None,
                     mesh: Optional[jax.sharding.Mesh] = None,
                     axis: Optional[str] = None) -> Array:
    """(n, D), (n,) -> (D,), or a (n, R, C) leaf view -> (R, C): streamed
    linear combination."""
    if backend in _SHARDED_BACKENDS:
        interpret = resolve_interpret()
        used, why = _pallas_used(interpret, sharded=True)
        tile = _tile(x, block_d, mesh, axis)
        record_decision("combine", backend, used, why, tile)
        return shardlib.sharded_combine(x, coeff, mesh=mesh, axis=axis,
                                        block_d=tile[0], interpret=interpret)
    if backend == "pallas":
        interpret = resolve_interpret()
        used, why = _pallas_used(interpret)
        tile = _tile(x, block_d)
        record_decision("combine", "pallas", used, why, tile)
        return _combine_op(x, coeff, block_d=_block_arg(x, block_d, tile),
                           interpret=interpret)
    record_decision("combine", backend, "xla")
    return _combine_op(x, coeff, use_pallas=False)


def dispatch_mixtrim(x: Array, m: Optional[Array], f, *, mode: str,
                     backend: str, block_d: Optional[int] = None,
                     mesh: Optional[jax.sharding.Mesh] = None,
                     axis: Optional[str] = None) -> Array:
    """(n, D) -> (D,), or a (n, R, C) leaf view -> (R, C): fused mix +
    coordinate trim/median.

    ``m=None`` elides the mix dot (plain CWTM/CWMed).  A TRACED f trims
    through the kernel's rank mask (one compile per fleet shape bucket).
    On a flat (n, D) view a non-power-of-two n runs the fused kernel
    through the sentinel-padded bitonic sort (recorded as a note, NOT a
    fallback — the kernel body executes for every n); a leaf view sorts
    slabs and pads nothing.
    """
    n = x.shape[0]
    # mode="med" ignores f, so a traced f shares the static kernel there.
    f = 0 if mode == "med" else f

    def _note(why: str, view: str) -> str:
        pad = _pad_note(n, view)
        return f"{why}; {pad}" if why and pad else (pad or why)

    if backend in _SHARDED_BACKENDS:
        interpret = resolve_interpret()
        used, why = _pallas_used(interpret, sharded=True)
        tile = _tile(x, block_d, mesh, axis)
        record_decision("mixtrim", backend, used, _note(why, tile[2]), tile)
        return shardlib.sharded_mixtrim(x, m, f, mode=mode, mesh=mesh,
                                        axis=axis, block_d=tile[0],
                                        interpret=interpret)
    if backend == "pallas":
        interpret = resolve_interpret()
        used, why = _pallas_used(interpret)
        tile = _tile(x, block_d)
        record_decision("mixtrim", "pallas", used, _note(why, tile[2]), tile)
        return _mixtrim_op(x, m, f, mode=mode,
                           block_d=_block_arg(x, block_d, tile),
                           interpret=interpret)
    record_decision("mixtrim", backend, "xla")
    return _mixtrim_op(x, m, f, mode=mode, use_pallas=False)


def dispatch_meamed(x: Array, m: Optional[Array], f, *, backend: str,
                    mesh: Optional[jax.sharding.Mesh] = None,
                    axis: Optional[str] = None) -> Array:
    """meamed on the flat buffer: no fused kernel exists, so the decision
    is always a RECORDED fallback — but the jnp form (the xla backend's
    own :func:`coordinate_rule`, so the arithmetic can never drift across
    backends) runs shard-locally under the sharded backend, keeping the
    wide intermediates at (n, D/k) per device.  ``m`` arrives pre-cast to
    the stack dtype (the bf16-parity contract of the caller)."""
    if backend in _SHARDED_BACKENDS:
        record_decision("mixtrim", backend, "xla",
                        "meamed has no fused kernel (shard-local jnp form)")
        return shardlib.sharded_meamed(x, m, f, mesh=mesh, axis=axis)
    record_decision("mixtrim", "pallas", "xla",
                    "meamed has no fused kernel")
    mixed = x if m is None else jnp.einsum(
        "mn,n...->m...", m, x, preferred_element_type=jnp.float32)
    return coordinate_rule(mixed, "meamed", f)
