"""Distributed robust aggregation over *pytrees* of per-worker stacks.

This is the first-class integration point of the paper's technique into the
training framework.  Inputs are pytrees whose every leaf carries a leading
worker axis ``n`` (sharded over the mesh worker axes by the caller via
``vmap(spmd_axis_name=...)``); the output is the aggregated pytree without
the worker axis, sharded like the parameters.

Two execution strategies (DESIGN.md §3):

* **gram path** (average / krum / multikrum / gm / mda, with or without
  NNM): accumulate the n x n Gram matrix leaf-by-leaf (GSPMD turns the
  leaf einsum into a worker-axis all-gather + model-sharded contraction),
  derive the linear-combination coefficients from G alone, and apply them
  leaf-by-leaf.  Peak memory: n x (largest leaf shard).
* **coordinate path** (cwtm / cwmed / meamed): optionally mix leaves with
  the NNM matrix (itself from the gram pass) then sort/trim along the
  worker axis, leaf-by-leaf.

Execution is backend-routed (``AggregatorSpec.backend`` through
:mod:`repro.kernels.dispatch`): the "xla" backend emits the leaf-streamed
jnp forms below (what the GSPMD distributed path lowers); the "pallas"
backend runs the blocked ``gram``, streamed ``combine`` and fused
``mixtrim`` kernels over each leaf's (n, R, C) leaf view, read in the
leaf's own tiled layout, so neither a concatenated or relaid-out copy of
the stack nor the NNM-mixed stack ``Y = M @ X`` materializes in HBM;
"pallas_sharded" runs them over ONE contiguous (n, D) buffer
shard_map'd along D over a mesh axis (per-shard gram +
psum'd (n, n) partials, replicated coefficients, shard-local
combine/mixtrim — :mod:`repro.kernels.shard`).  "auto" =
pallas on a single-device TPU, pallas_sharded on multi-device TPU, xla
elsewhere; see docs/perf.md.

Both paths do ranking-sensitive arithmetic in fp32.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.bucketing import (
    adjusted_f as _adjusted_f,
    bucket_counts as _bucket_counts,
    bucket_matrix as _bucket_matrix,
    clamp_bucket_size as _clamp_bucket_size,
    num_buckets as _num_buckets,
)
from repro.core import gram as gramlib
from repro.core.types import AggregatorSpec, COORDINATE_RULES, GRAM_RULES
from repro.kernels import dispatch as kdispatch
from repro.kernels.mixtrim.ref import coordinate_rule
from repro.obs import stages

Array = jax.Array
PyTree = Any


def tree_gram(tree: PyTree) -> Array:
    """Accumulate the (n, n) fp32 Gram matrix over all leaves.

    Leaves have shape (n, ...).  The per-leaf contraction is what GSPMD
    converts into the worker-axis all-gather; the n x n result replicates.
    """
    leaves = jax.tree_util.tree_leaves(tree)
    n = leaves[0].shape[0]
    g = jnp.zeros((n, n), dtype=jnp.float32)
    for leaf in leaves:
        # Contract in the leaf's own dtype (fp32 accumulate): when the
        # caller pre-cast the stack to bf16 for transport, the worker-axis
        # all-gather must move bf16 bytes — an eager astype(f32) here would
        # silently re-inflate the collective (measured; §Perf).
        flat = leaf.reshape(n, -1)
        g = g + jax.lax.dot_general(flat, flat, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    return g


def tree_sketch_gram(tree: PyTree, sketch_dim: int, key: Array) -> Array:
    """Gram matrix of a structured sketch of the stack (beyond-paper §Perf).

    Chunked signed-sum (CountSketch with bucket = position mod sketch_dim
    and random per-chunk signs): each worker folds its own rows into a
    (n, sketch_dim) sketch *locally* — O(d) work, O(sketch_dim) memory,
    and only the tiny sketch crosses the worker axis.  Distance RANKS —
    all NNM's neighbor selection needs — are preserved with high
    probability; coefficients are still applied to the exact stack.
    """
    leaves = jax.tree_util.tree_leaves(tree)
    n = leaves[0].shape[0]
    sk = jnp.zeros((n, sketch_dim), jnp.float32)
    for i, leaf in enumerate(leaves):
        flat = leaf.reshape(n, -1)
        d = flat.shape[1]
        pad = (-d) % sketch_dim
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        chunks = flat.reshape(n, -1, sketch_dim)
        kproj = jax.random.fold_in(key, i)
        signs = jax.random.rademacher(
            kproj, (chunks.shape[1],), dtype=jnp.float32)
        sk = sk + jnp.einsum("ncs,c->ns", chunks, signs,
                             preferred_element_type=jnp.float32)
    return sk @ sk.T


def tree_combine(tree: PyTree, coeff: Array) -> PyTree:
    """R = coeff @ X, leaf by leaf (contraction over the worker axis).

    The contraction runs in the leaf's dtype (fp32 accumulation) so bf16
    transport stacks are gathered as bf16 (see tree_gram note)."""
    def comb(leaf):
        return jnp.einsum("n,n...->...", coeff.astype(leaf.dtype), leaf,
                          preferred_element_type=jnp.float32)
    return jax.tree_util.tree_map(comb, tree)


def tree_mix(tree: PyTree, m: Array) -> PyTree:
    """Y = M @ X, leaf by leaf, keeping the worker axis (dtype-preserving,
    fp32 accumulation — see tree_gram note)."""
    def mix(leaf):
        return jnp.einsum("mn,n...->m...", m.astype(leaf.dtype), leaf,
                          preferred_element_type=jnp.float32)
    return jax.tree_util.tree_map(mix, tree)


def _tree_bucket(tree: PyTree, f, key: Array,
                 bucket_size: Optional[int]) -> tuple[PyTree, Any]:
    """Bucketing on pytrees: one shared permutation across all leaves.

    Ragged tails are handled exactly (paper: n=17, s=2 -> 9 buckets, one
    singleton): zero-pad and renormalize by true bucket occupancy.
    Dtype-preserving like :func:`repro.core.bucketing.bucketing`: means
    accumulate in (at least) fp32 and cast back to each leaf's dtype.
    A traced f needs an explicit ``bucket_size``."""
    leaves = jax.tree_util.tree_leaves(tree)
    n = leaves[0].shape[0]
    s = _clamp_bucket_size(n, bucket_size, f)
    perm = jax.random.permutation(key, n)
    n_buckets = _num_buckets(n, s)
    pad = n_buckets * s - n
    counts = _bucket_counts(n, s)

    def bucket(leaf):
        acc = jnp.promote_types(leaf.dtype, jnp.float32)
        x = leaf[perm].astype(acc)
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((pad,) + leaf.shape[1:], acc)])
        sums = x.reshape((n_buckets, s) + leaf.shape[1:]).sum(axis=1)
        means = sums / counts.astype(acc).reshape(
            (n_buckets,) + (1,) * (leaf.ndim - 1))
        return means.astype(leaf.dtype)

    return jax.tree_util.tree_map(bucket, tree), _adjusted_f(f, n_buckets)


def _hier_active(spec: AggregatorSpec) -> bool:
    """A hierarchical bucketing stage runs when the spec opts in OR the
    hierarchical backend is requested (the backend implies the stage)."""
    return bool(spec.hier) or spec.backend == "pallas_hier"


def _validate_hier(spec: AggregatorSpec) -> None:
    if spec.pre == "bucketing":
        raise ValueError(
            "hierarchical aggregation IS a bucketing stage; composing it "
            "with pre='bucketing' would bucket twice — use pre='nnm' or "
            "pre=None")
    if spec.sketch_dim:
        raise ValueError(
            "hierarchical aggregation is incompatible with sketch_dim: the "
            "signed-sketch gram has no reduced-population form (the fused "
            "bucketgram kernel already removes the wide gram pass)")


_HIER_S1_NOTE = "s=1: singleton buckets, identity reduction (skipped)"


def _hier_reduce(segs: list, layout, spec: AggregatorSpec, f, *,
                 key: Optional[Array], backend: str,
                 mesh, worker_axis: Optional[str], axis: Optional[str]
                 ) -> tuple[list, Any, Optional[Array]]:
    """The hierarchical pre-reduction of the stack's segments (leaf views
    or one (n, D) buffer).

    Returns ([reduced stack (ceil(n/s), D)], adjusted f, reduced fp32 Gram
    or None).  The permutation rides inside the (n_b, n) assignment matrix
    built from ``key`` in-graph, so the compiled kernel is key-independent
    (one compile per fleet shape bucket).  s=1 short-circuits to the
    identity — singleton buckets make the permutation semantically inert,
    and skipping it (segments untouched) keeps hier(s=1) BITWISE equal to
    the dense pipeline.
    """
    n = segs[0].shape[0]
    if key is None:
        raise ValueError("hierarchical aggregation requires a PRNG key")
    s = _clamp_bucket_size(n, spec.bucket_size, f)
    if s == 1:
        kdispatch.record_decision("bucketgram", backend, "skipped",
                                  _HIER_S1_NOTE)
        return segs, f, None
    # The mesh backends' one (n, D) buffer as it is; split views back in
    # their leaves' order, in one buffer.
    flat = kdispatch.flat_stack(segs, layout)
    n_b = _num_buckets(n, s)
    bmat = _bucket_matrix(key, n, s, dtype=jnp.float32)
    need_gram = spec.rule in GRAM_RULES or spec.pre == "nnm"
    y, g = kdispatch.dispatch_bucketgram(
        flat, bmat, backend=backend, with_gram=need_gram, mesh=mesh,
        worker_axis=worker_axis, axis=axis)
    return [y], _adjusted_f(f, n_b), g


def _aggregate_flat(work: PyTree, spec: AggregatorSpec, f, *,
                    key: Optional[Array], return_coeff: bool,
                    backend: str = "pallas",
                    mesh_ctx: Optional[tuple] = None,
                    internals: Optional[dict] = None,
                    hier: bool = False) -> PyTree:
    """Kernel-backend pipeline: pre-aggregated stack -> segments ->
    blocked gram -> coeff -> streamed combine / fused mixtrim ->
    aggregated pytree.  The mesh backends and the hierarchical stage
    stream ONE contiguous (n, D) buffer; single-device "pallas" streams
    each leaf's (n, R, C) leaf view in its own layout and sums the
    per-leaf Grams, so the stack is neither copied nor relaid out (only
    its bucketed reduction concatenates).

    ``backend`` is "pallas" (single device), "pallas_sharded" (the
    shard_map'd form; ``mesh_ctx`` is its resolved (mesh, axis) — the
    gram psums tiny (n, n) partials and combine/mixtrim run shard-local,
    while the O(n^2) coefficient/NNM math below stays replicated), or
    "pallas_hier" (``mesh_ctx`` = (mesh, worker_axis | None, model_axis);
    the stack shards along workers x D and the fused bucketgram kernel
    reduces it before everything below runs on the ceil(n/s) population).
    ``f`` is a python int or a traced int32 scalar (the fleet path;
    rank-mask kernels keep one compile per shape bucket).  Decisions land
    on ``kdispatch.last_dispatch()``.
    """
    if backend == "pallas":
        segs, layout = kdispatch.split_worker_stack(work)
    else:
        flat, layout = kdispatch.flatten_worker_stack(work)
        segs = [flat]
    if backend == "pallas_hier":
        mesh, worker_axis, axis = mesh_ctx
    else:
        mesh, axis = mesh_ctx if mesh_ctx is not None else (None, None)
        worker_axis = None

    g = None
    if hier:
        # The fused reduction emits the reduced stack AND (when a gram
        # consumer follows) its Gram in the same pass — the gram stage
        # below is skipped.
        segs, f, g = _hier_reduce(
            segs, layout, spec, f, key=key, backend=backend,
            mesh=mesh, worker_axis=worker_axis, axis=axis)

    mix_matrix = None
    if (spec.rule in GRAM_RULES or spec.pre == "nnm") and g is None:
        if spec.sketch_dim and key is not None:
            # The sketch gram folds per-chunk signs per LEAF index — a
            # contract shared with the xla backend — so it stays on the
            # leaf-streamed path; only exact grams use the blocked kernel.
            kdispatch.record_decision(
                "gram", backend, "xla",
                "sketch_dim gram runs the leaf-streamed signed sketch")
            g = tree_sketch_gram(work, spec.sketch_dim, key)
        else:
            # Leaf views smallest first: the order in which the compiled
            # step holds the least HBM (PERF.md, section 6).
            order = (sorted(segs, key=lambda x: x.size)
                     if segs[0].ndim == 3 else segs)
            g = sum(kdispatch.dispatch_gram(x, backend=backend,
                                            mesh=mesh, axis=axis)
                    for x in order)

    if spec.pre == "nnm":
        d2 = gramlib.pdist_sq_from_gram(g)
        mix_matrix = gramlib.nnm_matrix(d2, f)
        if internals is not None:
            internals["mix_matrix"] = mix_matrix
        g = gramlib.mixed_gram(g, mix_matrix)

    if spec.rule in GRAM_RULES:
        if spec.rule == "autogm":
            # The gram and combine stages still run the blocked kernels;
            # only the adaptive-weight solve itself (replicated O(n^2)
            # alternating Weiszfeld + simplex projection on G) has no
            # kernel form.  Recorded so a pallas-requested autogm round is
            # never silently partial.
            kdispatch.record_decision(
                "autogm_coeff", backend, "xla",
                "autogm adaptive-weight solve is replicated gram-space "
                "math with no kernel form")
        coeff = gramlib.coeff_for_rule(
            spec.rule, g, f, gm_iters=spec.gm_iters, gm_eps=spec.gm_eps,
            autogm_lamb=spec.autogm_lamb, autogm_iters=spec.autogm_iters)
        if mix_matrix is not None:
            coeff = coeff @ mix_matrix   # R = c^T (M X) = (c^T M) X
        vecs = [kdispatch.dispatch_combine(x, coeff, backend=backend,
                                           mesh=mesh, axis=axis)
                for x in segs]
        out = kdispatch.unflatten_aggregate(vecs, layout)
        return (out, coeff) if return_coeff else out

    if spec.rule in COORDINATE_RULES:
        # No NNM -> m=None: the kernel elides the mix dot instead of
        # paying an identity matmul per tile.  With NNM, M is cast to the
        # segment dtype first — the same rounding tree_mix applies — so
        # bf16-transport runs agree across backends.
        def mix_for(x):
            return None if mix_matrix is None else mix_matrix.astype(x.dtype)

        if segs[0].ndim == 3:
            # Start the coordinate kernels once every leaf view exists (with
            # NNM, M already waits for all of them): left free to interleave
            # them with the attack, XLA holds more of the stack's honest rows
            # at once (PERF.md, section 6).
            segs = list(jax.lax.optimization_barrier(tuple(segs)))

        if spec.rule == "meamed":
            # No fused kernel: mix (if any) + mean-around-median in jnp —
            # shard-local under the sharded backend, per segment
            # otherwise.  Recorded so kernel-path callers see it.
            vecs = [kdispatch.dispatch_meamed(x, mix_for(x), f,
                                              backend=backend,
                                              mesh=mesh, axis=axis)
                    for x in segs]
        else:
            mode = "med" if spec.rule == "cwmed" else "trim"
            vecs = [kdispatch.dispatch_mixtrim(x, mix_for(x), f, mode=mode,
                                               backend=backend,
                                               mesh=mesh, axis=axis)
                    for x in segs]
        out = kdispatch.unflatten_aggregate(vecs, layout)
        return (out, None) if return_coeff else out

    raise ValueError(f"unknown rule {spec.rule!r}")


def _open_routed_record(spec: AggregatorSpec, f
                        ) -> tuple[str, Optional[tuple]]:
    """Resolve the backend (+ shard mesh), open the dispatch record (its
    ``dyn`` says whether ``f`` is traced), and record a degrade when
    "pallas_sharded" / "pallas_hier" has no multi-device mesh.

    Returns (effective backend, mesh_ctx) where mesh_ctx is the resolved
    (mesh, axis) for the sharded backend, (mesh, worker_axis, model_axis)
    for the hierarchical backend, and None otherwise."""
    hier = _hier_active(spec)
    backend = kdispatch.resolve_backend(spec.backend, hier=hier)
    mesh_ctx = None
    degraded = None
    if backend == "pallas_hier":
        mesh_ctx = kdispatch.resolve_hier_mesh()
        if mesh_ctx is None:
            # The hier STAGE survives the degrade — only the mesh form
            # does not — so the dense (leaf-streamed) bucketing path runs.
            backend = "xla"
            degraded = ("pallas_hier",
                        "no multi-device mesh: dense bucketing path")
    elif backend == "pallas_sharded":
        mesh_ctx = kdispatch.resolve_shard_mesh()
        if mesh_ctx is None:
            backend = "xla"
            degraded = ("pallas_sharded",
                        "no multi-device mesh: leaf-streamed fallback")
    if mesh_ctx is None:
        mesh_devices, mesh_axis, worker_axis = 1, None, None
    elif len(mesh_ctx) == 3:
        mesh, worker_axis, mesh_axis = mesh_ctx
        mesh_devices = kdispatch.shardlib.axis_size(mesh, mesh_axis)
        if worker_axis is not None:
            mesh_devices *= kdispatch.shardlib.axis_size(mesh, worker_axis)
    else:
        mesh_devices = kdispatch.shardlib.axis_size(*mesh_ctx)
        mesh_axis, worker_axis = mesh_ctx[1], None
    rec = kdispatch.open_record(
        requested=spec.backend, backend=backend, rule=spec.rule,
        pre=spec.pre, mesh_devices=mesh_devices,
        mesh_axis=mesh_axis, hier=hier, bucket_size=spec.bucket_size,
        mesh_worker_axis=worker_axis)
    rec.dyn = not isinstance(f, int)
    if degraded is not None:
        kdispatch.record_decision("pipeline", degraded[0], "xla",
                                  degraded[1])
    return backend, mesh_ctx


def _aggregate_stage(fn):
    """Trace ``fn`` as the ``aggregate`` stage of the robust step
    (:mod:`repro.obs.stages`); the stage is looked up at every call."""
    @functools.wraps(fn)
    def staged(*args, **kwargs):
        with stages.stage("aggregate"):
            return fn(*args, **kwargs)
    return staged


@_aggregate_stage
def robust_aggregate(tree: PyTree, spec: AggregatorSpec, *, f=None,
                     key: Optional[Array] = None,
                     return_coeff: bool = False,
                     internals: Optional[dict] = None) -> PyTree:
    """Full distributed pipeline: pre-aggregation + rule on a worker-stacked
    pytree.  Returns the aggregated pytree (worker axis removed).

    ``f`` is the Byzantine count, ``spec.f`` when None: a python int, or a
    TRACED int32 scalar (a fleet lane's budget, possibly a vmap tracer) so
    that one compiled aggregation serves every budget.  It is given here,
    never inside ``spec``, which is hashed as jit key material.  The
    rule, pre-aggregation and bucket size stay static; with a traced f,
    trimming and neighbor selection go through rank masks instead of
    static slices, bucketing needs an explicit ``spec.bucket_size`` (the
    floor(n/2f) default is shape-level), and MDA has no traced form.

    With ``return_coeff=True`` additionally returns the effective linear
    coefficient vector when one exists (gram rules), else None — used by the
    kappa-hat diagnostics.

    ``internals`` (taps support): pass an empty dict and the pipeline
    stashes its reusable intermediates into it — ``"mix_matrix"`` (the
    fp32 NNM matrix), and on the XLA backend also ``"mixed"`` (the
    NNM-mixed worker stack) and ``"sorted_leaves"`` (cwtm's per-leaf
    sorted stacks).  :func:`repro.obs.taps.health_taps` consumes these so
    tapped rounds never recompute the O(n^2 d) passes (relying on XLA CSE
    instead is NOT sufficient: inside ``lax.scan`` bodies the duplicated
    NNM construction fuses per-consumer before CSE can merge the dominant
    sort/dot ops — measured at ~2x round cost).

    Execution routes through the kernel backend layer per
    ``spec.backend`` (see :mod:`repro.kernels.dispatch`).
    """
    if f is None:
        f = spec.f
    elif not isinstance(f, int):
        f = jnp.asarray(f, jnp.int32)
    work = tree
    mix_matrix = None
    hier = _hier_active(spec)
    if hier:
        _validate_hier(spec)

    if spec.pre == "bucketing":
        if key is None:
            raise ValueError("bucketing requires a PRNG key")
        work, f = _tree_bucket(work, f, key, spec.bucket_size)

    if spec.transport_dtype == "bf16":
        # Halve the worker-axis all-gather bytes; coefficient math below
        # stays fp32 (EXPERIMENTS.md §Perf).
        work = jax.tree_util.tree_map(
            lambda l: l.astype(jnp.bfloat16), work)

    backend, mesh_ctx = _open_routed_record(spec, f)
    if backend in ("pallas", "pallas_sharded", "pallas_hier"):
        return _aggregate_flat(work, spec, f, key=key,
                               return_coeff=return_coeff,
                               backend=backend, mesh_ctx=mesh_ctx,
                               internals=internals, hier=hier)
    kdispatch.record_decision("pipeline", "xla", "xla",
                              "leaf-streamed jnp path (GSPMD-friendly)")

    if hier:
        # Dense hierarchical stage (gather form), sharing the SAME key —
        # and so the same bucket grouping — as the fused kernel path.
        if key is None:
            raise ValueError("hierarchical aggregation requires a PRNG key")
        n = jax.tree_util.tree_leaves(work)[0].shape[0]
        s = _clamp_bucket_size(n, spec.bucket_size, f)
        if s == 1:
            kdispatch.record_decision("bucketgram", "xla", "skipped",
                                      _HIER_S1_NOTE)
        else:
            kdispatch.record_decision(
                "bucketgram", "xla", "xla",
                "dense leaf-streamed bucketing (gather form)")
            work, f = _tree_bucket(work, f, key, s)

    if spec.sketch_dim and key is not None:
        g = tree_sketch_gram(work, spec.sketch_dim, key)
    else:
        g = tree_gram(work)

    if spec.pre == "nnm":
        d2 = gramlib.pdist_sq_from_gram(g)
        mix_matrix = gramlib.nnm_matrix(d2, f)
        if internals is not None:
            internals["mix_matrix"] = mix_matrix
        # Gram of the mixed stack is M G M^T — free, no second data pass.
        g = gramlib.mixed_gram(g, mix_matrix)

    if spec.rule in GRAM_RULES:
        coeff = gramlib.coeff_for_rule(spec.rule, g, f,
                                       gm_iters=spec.gm_iters,
                                       gm_eps=spec.gm_eps,
                                       autogm_lamb=spec.autogm_lamb,
                                       autogm_iters=spec.autogm_iters)
        if mix_matrix is not None:
            coeff = coeff @ mix_matrix   # R = c^T (M X) = (c^T M) X
        out = tree_combine(work, coeff)
        return (out, coeff) if return_coeff else out

    if spec.rule in COORDINATE_RULES:
        if mix_matrix is not None:
            work = tree_mix(work, mix_matrix)
            if internals is not None:
                internals["mixed"] = work
        sorted_leaves = [] if internals is not None else None
        out = jax.tree_util.tree_map(
            lambda leaf: coordinate_rule(leaf, spec.rule, f, sorted_leaves),
            work)
        if sorted_leaves:
            # cwtm's per-leaf sorted stacks, for the taps to reuse.
            internals["sorted_leaves"] = sorted_leaves
        if return_coeff:
            return out, None
        return out

    raise ValueError(f"unknown rule {spec.rule!r}")
