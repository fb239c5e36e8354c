"""Kernel backend layer: routing, parity, fallback detectability.

The load-bearing acceptance tests:

* ``backend="pallas"`` (interpret mode on CPU) matches ``backend="xla"``
  on ``robust_aggregate`` outputs for every rule x pre combination;
* the same pipeline holds the same parity with f traced, a traced f
  aggregates as the python int does, and one compile serves every f (the
  fleet shape-bucket contract);
* a requested-pallas run that silently fell back to the jnp oracle is
  DETECTABLE through ``last_dispatch()``;
* the fused mixtrim path structurally eliminates the materialized
  (n, D) mixed stack (no full-width dot_general/sort in the jaxpr).
"""
import ast
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AggregatorSpec
from repro.core import robust as robust_lib
from repro.kernels import dispatch as kdispatch
from repro.kernels import tiling

ALL_RULES = ("average", "krum", "multikrum", "gm", "mda",
             "cwtm", "cwmed", "meamed")
DYN_RULES = tuple(r for r in ALL_RULES if r != "mda")
PRES = (None, "nnm", "bucketing")


def _tree(seed=0, n=16):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(n, 37)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(n, 3, 5)), jnp.float32),
            "s": jnp.asarray(rng.normal(size=(n,)), jnp.float32)}


def _assert_trees_close(a, b, **kw):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), **kw)


# ---------------------------------------------------------------------------
# Acceptance: pallas == xla for every rule x pre, static and dynamic f.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule", ALL_RULES)
@pytest.mark.parametrize("pre", PRES)
def test_backend_parity_static(rule, pre):
    tree, key = _tree(3), jax.random.PRNGKey(5)
    for f in (0, 3):
        def spec(backend):
            return AggregatorSpec(rule=rule, f=f, pre=pre, bucket_size=2,
                                  backend=backend)
        ref = robust_lib.robust_aggregate(tree, spec("xla"), key=key)
        got = robust_lib.robust_aggregate(tree, spec("pallas"), key=key)
        _assert_trees_close(got, ref, rtol=1e-5, atol=1e-5,
                            err_msg=f"{rule}/{pre}/f={f}")


@pytest.mark.parametrize("rule", DYN_RULES)
@pytest.mark.parametrize("pre", PRES)
def test_backend_parity_dyn(rule, pre):
    tree, key = _tree(4), jax.random.PRNGKey(6)
    for f in (0, 2, 3):
        def spec(backend):
            return AggregatorSpec(rule=rule, f=f, pre=pre, bucket_size=2,
                                  backend=backend)
        ref = robust_lib.robust_aggregate(tree, spec("xla"),
                                          f=jnp.int32(f), key=key)
        got = robust_lib.robust_aggregate(tree, spec("pallas"),
                                          f=jnp.int32(f), key=key)
        _assert_trees_close(got, ref, rtol=1e-5, atol=1e-5,
                            err_msg=f"{rule}/{pre}/f={f}")


@pytest.mark.parametrize("rule", DYN_RULES)
@pytest.mark.parametrize("pre", PRES)
@pytest.mark.parametrize("backend", ("xla", "pallas"))
def test_traced_f_matches_int_f(rule, pre, backend):
    """One entry for both kinds of f: a traced f (rank masks) aggregates
    as the python int (static slices) does, and the record says which."""
    tree, key = _tree(12), jax.random.PRNGKey(13)
    for f in (0, 2, 3):
        spec = AggregatorSpec(rule=rule, f=0, pre=pre, bucket_size=2,
                              backend=backend)
        want = robust_lib.robust_aggregate(tree, spec, f=f, key=key)
        assert not kdispatch.last_dispatch().dyn
        got = robust_lib.robust_aggregate(tree, spec, f=jnp.int32(f),
                                          key=key)
        assert kdispatch.last_dispatch().dyn
        _assert_trees_close(got, want, rtol=1e-5, atol=1e-5,
                            err_msg=f"{rule}/{pre}/{backend}/f={f}")


def test_kernels_import_nothing_from_core():
    """The kernel layer sits below ``repro.core``: no module under
    ``repro.kernels`` imports it, at top level or lazily."""
    root = Path(kdispatch.__file__).parent
    offenders = []
    for path in sorted(root.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            else:
                continue
            offenders += [f"{path.relative_to(root)}: {name}"
                          for name in names
                          if name == "repro.core"
                          or name.startswith("repro.core.")]
    assert not offenders, offenders


def test_batched_pallas_matches_per_lane_dyn():
    tree = _tree(7)
    fs = jnp.asarray([0, 2, 3], jnp.int32)
    bt = jax.tree_util.tree_map(
        lambda leaf: jnp.stack([leaf, 2 * leaf, leaf + 1]), tree)
    spec = AggregatorSpec(rule="cwtm", f=0, pre="nnm", backend="pallas")
    out = jax.vmap(lambda t, f: robust_lib.robust_aggregate(t, spec, f=f))(
        bt, fs)
    for lane, f in enumerate((0, 2, 3)):
        single = robust_lib.robust_aggregate(
            jax.tree_util.tree_map(lambda leaf, k=lane: leaf[k], bt),
            spec, f=jnp.int32(f))
        _assert_trees_close(
            jax.tree_util.tree_map(lambda leaf, k=lane: leaf[k], out),
            single, rtol=1e-5, atol=1e-6)


def test_backend_parity_bf16_transport():
    """bf16 transport stacks flow through the kernels as bf16 bytes and
    keep parity with the leaf-streamed xla pipeline.  Tight tolerance:
    the NNM matrix is cast to the stack dtype on BOTH paths (identical
    rounding of the mixing weights), leaving only fp32 sum-order noise."""
    tree, key = _tree(8), jax.random.PRNGKey(9)
    for rule in ("cwtm", "cwmed", "krum", "gm", "meamed"):
        def spec(backend):
            return AggregatorSpec(rule=rule, f=3, pre="nnm",
                                  transport_dtype="bf16", backend=backend)
        ref = robust_lib.robust_aggregate(tree, spec("xla"), key=key)
        got = robust_lib.robust_aggregate(tree, spec("pallas"), key=key)
        _assert_trees_close(got, ref, rtol=1e-3, atol=1e-3, err_msg=rule)


def test_return_coeff_through_pallas_backend():
    tree = _tree(10)
    spec = AggregatorSpec(rule="multikrum", f=3, pre="nnm", backend="pallas")
    out, coeff = robust_lib.robust_aggregate(tree, spec, return_coeff=True)
    ref, ref_coeff = robust_lib.robust_aggregate(
        tree, AggregatorSpec(rule="multikrum", f=3, pre="nnm",
                             backend="xla"), return_coeff=True)
    np.testing.assert_allclose(np.asarray(coeff), np.asarray(ref_coeff),
                               rtol=1e-5, atol=1e-6)
    _assert_trees_close(out, ref, rtol=1e-5, atol=1e-5)
    _, coeff2 = robust_lib.robust_aggregate(
        tree, AggregatorSpec(rule="cwtm", f=3, pre="nnm", backend="pallas"),
        return_coeff=True)
    assert coeff2 is None   # coordinate rules have no coefficient vector


# ---------------------------------------------------------------------------
# One compile serves every f of a shape bucket (dynamic-f contract).
# ---------------------------------------------------------------------------

def test_dyn_pallas_one_compile_across_f():
    tree = _tree(11)
    spec = AggregatorSpec(rule="cwtm", f=0, pre="nnm", backend="pallas")
    traces = []

    @jax.jit
    def agg(t, f):
        traces.append(1)
        return robust_lib.robust_aggregate(t, spec, f=f)

    for f in (0, 1, 2, 3, 5, 7):
        got = agg(tree, jnp.int32(f))
        ref = robust_lib.robust_aggregate(
            tree, AggregatorSpec(rule="cwtm", f=0, pre="nnm",
                                 backend="xla"), f=jnp.int32(f))
        _assert_trees_close(got, ref, rtol=1e-5, atol=1e-5,
                            err_msg=f"f={f}")
    assert len(traces) == 1, f"expected one trace, got {len(traces)}"


# ---------------------------------------------------------------------------
# Dispatch record: silent fallbacks are detectable.
# ---------------------------------------------------------------------------

def test_nonpow2_mixtrim_runs_fused_padded_kernel():
    """n=17 (paper scale) on backend="pallas": the padded sentinel sort
    lets the fused kernel run — ZERO recorded fallbacks, the pad is noted
    for observability, and the result matches the xla oracle.  (n=17 is
    past ``tiling.leaf_view_fits``: its leaves go as flat views.)"""
    tree = _tree(12, n=17)
    spec = AggregatorSpec(rule="cwtm", f=4, pre="nnm", backend="pallas")
    got = robust_lib.robust_aggregate(tree, spec)
    rec = kdispatch.last_dispatch()
    assert rec is not None and rec.backend == "pallas"
    assert rec.fallbacks == [], rec.describe()
    assert any(d.primitive == "mixtrim" and "padded to 32" in d.reason
               for d in rec.decisions), rec.describe()
    assert {d.view for d in rec.decisions} == {"flat"}, rec.describe()
    ref = robust_lib.robust_aggregate(
        tree, AggregatorSpec(rule="cwtm", f=4, pre="nnm", backend="xla"))
    _assert_trees_close(got, ref, rtol=1e-5, atol=1e-5)


def test_pow2_run_records_no_fallback():
    tree = _tree(13, n=16)
    spec = AggregatorSpec(rule="cwtm", f=3, pre="nnm", backend="pallas")
    robust_lib.robust_aggregate(tree, spec)
    rec = kdispatch.last_dispatch()
    assert rec.fallbacks == [], rec.describe()
    used = {d.primitive: d.used for d in rec.decisions}
    # off-TPU the kernels run interpreted — recorded as pallas-interpret,
    # which is NOT a fallback (the kernel body executed)
    expected = "pallas" if jax.default_backend() == "tpu" \
        else "pallas-interpret"
    assert used["gram"] == expected and used["mixtrim"] == expected


def test_meamed_fallback_is_recorded():
    tree = _tree(14)
    robust_lib.robust_aggregate(
        tree, AggregatorSpec(rule="meamed", f=3, pre="nnm",
                             backend="pallas"))
    rec = kdispatch.last_dispatch()
    assert any("meamed" in d.reason for d in rec.fallbacks), rec.describe()


def test_xla_backend_records_xla_pipeline():
    tree = _tree(15)
    robust_lib.robust_aggregate(
        tree, AggregatorSpec(rule="cwtm", f=3, pre="nnm", backend="xla"))
    rec = kdispatch.last_dispatch()
    assert rec.backend == "xla" and rec.fallbacks == []


def test_resolve_backend():
    assert kdispatch.resolve_backend("xla") == "xla"
    assert kdispatch.resolve_backend("pallas") == "pallas"
    assert kdispatch.resolve_backend("pallas_sharded") == "pallas_sharded"
    # auto: pallas on a single-device TPU, pallas_sharded on multi-device
    # TPU hosts, xla elsewhere (interpret kernels are not a fast path)
    auto = kdispatch.resolve_backend("auto")
    if jax.default_backend() == "tpu":
        assert auto == ("pallas" if jax.device_count() == 1
                        else "pallas_sharded")
    else:
        assert auto == "xla"
    with pytest.raises(ValueError, match="backend"):
        kdispatch.resolve_backend("cuda")
    with pytest.raises(ValueError, match="backend"):
        robust_lib.robust_aggregate(
            _tree(16), AggregatorSpec(rule="cwtm", f=3, backend="cuda"))


def test_pallas_sharded_degrade_is_recorded():
    """A "pallas_sharded" request on a host with no multi-device mesh must
    still compute correctly AND leave a detectable trail: the record shows
    backend="xla", mesh_devices=1, and a pipeline-level fallback."""
    tree = _tree(18)
    spec = AggregatorSpec(rule="cwtm", f=3, pre="nnm",
                          backend="pallas_sharded")
    got = robust_lib.robust_aggregate(tree, spec)
    rec = kdispatch.last_dispatch()
    if jax.device_count() > 1:     # forced-multi-device hosts: no degrade
        assert rec.backend == "pallas_sharded" and rec.mesh_devices > 1
        return
    assert rec.requested == "pallas_sharded" and rec.backend == "xla"
    assert rec.mesh_devices == 1 and rec.mesh_axis is None
    assert any(d.primitive == "pipeline" and d.fell_back
               for d in rec.decisions), rec.describe()
    ref = robust_lib.robust_aggregate(
        tree, AggregatorSpec(rule="cwtm", f=3, pre="nnm", backend="xla"))
    _assert_trees_close(got, ref, rtol=1e-6, atol=1e-6)


def test_dispatch_gram_batched_direct_entry():
    """The direct (B, n, d) gram entry: kernel result per lane equals the
    solo dispatch, and the decision is recorded."""
    x = jnp.asarray(np.random.default_rng(21).normal(size=(3, 16, 200)),
                    jnp.float32)
    kdispatch.open_record(requested="pallas", backend="pallas",
                          rule="gram", pre=None)
    got = kdispatch.dispatch_gram_batched(x, backend="pallas")
    rec = kdispatch.last_dispatch()
    assert any(d.primitive == "gram_batched" and not d.fell_back
               for d in rec.decisions)
    for k in range(3):
        np.testing.assert_array_equal(
            np.asarray(got[k]),
            np.asarray(kdispatch.dispatch_gram(x[k], backend="pallas")))
    ref = kdispatch.dispatch_gram_batched(x, backend="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# Flatten / unflatten and block_d selection.
# ---------------------------------------------------------------------------

def test_flatten_roundtrip_preserves_layout():
    tree = _tree(17)
    flat, layout = kdispatch.flatten_worker_stack(tree)
    assert flat.shape == (16, layout.width)
    assert layout.n == 16 and layout.width == 37 + 15 + 1
    # combining with a one-hot coefficient reproduces that worker's row
    onehot = jnp.zeros((16,)).at[4].set(1.0)
    picked = kdispatch.unflatten_aggregate(flat.T @ onehot, layout)
    _assert_trees_close(
        picked, jax.tree_util.tree_map(lambda leaf: leaf[4], tree),
        rtol=1e-6, atol=1e-6)


#: Narrow (one tile below a chunk), ragged (several chunks, d not a
#: multiple of 128) and wide (a large SmolLM-360M leaf: many grid steps).
PICK_WIDTHS = (300, 5000, 78_643_200)


@pytest.mark.parametrize("n", [4, 17, 256])
@pytest.mark.parametrize("d", PICK_WIDTHS)
def test_pick_block_d(n, d):
    """The grid tile comes from (n, d, dtype) and the VMEM budget alone:
    a multiple of 128, at most d rounded up to 128 lanes, inside the
    budget, a multiple of the chunk whenever d takes several grid steps,
    and never narrower for fewer workers."""
    w = kdispatch.pick_block_d(n, d)
    assert w % 128 == 0 and 128 <= w <= -(-d // 128) * 128
    assert tiling.vmem_bytes(n, w) <= tiling.VMEM_BUDGET
    if w < d:
        assert w % tiling.CHUNK == 0
    for fewer in (m for m in (4, 17, 256) if m < n):
        assert kdispatch.pick_block_d(fewer, d) >= w
    if d == PICK_WIDTHS[-1]:
        # a wide stack gets a wide tile, shrinking as n grows: no longer
        # the one 512-lane cap for every n
        assert w > tiling.CHUNK
        for fewer in (m for m in (4, 17, 256) if m < n):
            assert kdispatch.pick_block_d(fewer, d) > w
    # the wide tile of a narrow dtype is never narrower
    assert kdispatch.pick_block_d(n, d, jnp.bfloat16) >= w


def test_dispatch_history_records_tile_and_grid_steps():
    """Each kernel decision carries the grid tile W its leaf streamed with
    and the number of grid steps: leaves of one tile share a decision,
    leaves of another tile get their own."""
    n = 4
    tree = {"wide": jnp.ones((n, 2600), jnp.float32),
            "narrow": jnp.ones((n, 300), jnp.float32)}
    spec = AggregatorSpec(rule="cwtm", f=1, pre="nnm", backend="pallas")
    robust_lib.robust_aggregate(tree, spec)
    rec = kdispatch.dispatch_history(1)[0]
    tiles = {(d.primitive, d.block_d, d.grid_steps) for d in rec.decisions
             if d.primitive in ("gram", "mixtrim")}
    want = set()
    for prim in ("gram", "mixtrim"):
        for d in (2600, 300):
            w = min(kdispatch.pick_block_d(n, d), d)
            want.add((prim, w, -(-d // w)))
    assert tiles == want, rec.describe()
    assert "leaf (n,R,C) W=2600 steps=1" in rec.describe()
    assert {d.view for d in rec.decisions
            if d.primitive in ("gram", "mixtrim")} == {"leaf"}
    x = jnp.ones((n, 2600), jnp.float32)
    kdispatch.open_record(requested="pallas", backend="pallas", rule="cwtm",
                          pre=None)
    kdispatch.dispatch_gram(x, backend="pallas", block_d=1024)
    kdispatch.dispatch_gram(x, backend="xla")
    got = [(d.used.split("-")[0], d.block_d, d.grid_steps, d.view)
           for d in kdispatch.last_dispatch().decisions]
    assert got == [("pallas", 1024, 3, "flat"), ("xla", None, None, None)]


# ---------------------------------------------------------------------------
# Structural: the fused path removes the materialized mixed stack.
# ---------------------------------------------------------------------------

def test_fused_mixtrim_eliminates_mixed_stack():
    """XLA's nnm+cwtm materializes two full-width (n, D) intermediates
    (the Y = M @ X dot and the sort); the fused kernel path has ZERO —
    its jaxpr only ever holds (n, BLK_D) tiles."""
    n, d = 16, 8192
    tree = {"x": jnp.zeros((n, d), jnp.float32)}

    def counts(backend):
        spec = AggregatorSpec(rule="cwtm", f=3, pre="nnm", backend=backend)
        return kdispatch.count_wide_ops(
            lambda t: robust_lib.robust_aggregate(t, spec), tree,
            n=n, width=d)

    assert counts("xla") >= 2
    assert counts("pallas") == 0


def test_single_device_pallas_streams_leaves_in_place():
    """backend="pallas" runs the kernels on each leaf's (n, d_i) view: no
    concatenated (n, D) copy of the stack (at full model width that copy
    does not fit one chip), each primitive recorded once per leaf tile
    (here each leaf is one tile of its own width), and the result equal to
    xla's.  The mesh backends still stream one flat buffer."""
    tree = _tree(19)
    width = 37 + 15 + 1
    for rule in ("cwtm", "gm"):
        spec = AggregatorSpec(rule=rule, f=3, pre="nnm", backend="pallas")
        assert kdispatch.count_wide_ops(
            lambda t: robust_lib.robust_aggregate(t, spec), tree,
            n=16, width=width, primitives=("concatenate",)) == 0
        got = robust_lib.robust_aggregate(tree, spec)
        decs = kdispatch.last_dispatch().decisions
        keys = [(d.primitive, d.block_d, d.grid_steps) for d in decs]
        assert sorted(keys, key=str) == sorted(set(keys), key=str), keys
        assert {d.block_d for d in decs if d.primitive == "gram"} \
            == {37, 15, 1}, keys
        ref = robust_lib.robust_aggregate(
            tree, AggregatorSpec(rule=rule, f=3, pre="nnm", backend="xla"))
        _assert_trees_close(got, ref, rtol=1e-5, atol=1e-5, err_msg=rule)


def test_split_worker_stack_views_every_leaf():
    """Each leaf's (n, R, C) leaf view: C the minor dim of the leaf's TPU
    layout (its last, or its second-to-last where the TPU holds the two
    swapped), R the product of the others, a 2-D leaf (n, 1, C), a 1-D
    one (n, 1, 1); the views go back to the leaves' order exactly."""
    tree = _tree(20, n=5)
    tree["tall"] = jnp.asarray(
        np.random.default_rng(3).normal(size=(5, 2, 3, 16, 7)), jnp.float32)
    segs, layout = kdispatch.split_worker_stack(tree)
    flat, flat_layout = kdispatch.flatten_worker_stack(tree)
    assert layout == flat_layout
    views = {(37,): (5, 1, 37), (3, 5): (5, 3, 5), (): (5, 1, 1),
             (2, 3, 16, 7): (5, 42, 16)}       # (16, 7) is held 16-minor
    assert [s.shape for s in segs] == [
        views[shape] for _, _, shape in layout.segments]
    assert [s.shape[1] * s.shape[2] for s in segs] == [
        size for _, size, _ in layout.segments]
    np.testing.assert_array_equal(kdispatch.flat_stack(segs, layout), flat)
    np.testing.assert_array_equal(
        segs[sorted(tree).index("tall")],
        np.swapaxes(np.asarray(tree["tall"]), -1, -2).reshape(5, 42, 16))
    # per-leaf aggregates rebuild the same tree as the flat one
    per_leaf = kdispatch.unflatten_aggregate([s[2] for s in segs], layout)
    _assert_trees_close(per_leaf,
                        kdispatch.unflatten_aggregate(flat[2], layout),
                        rtol=0, atol=0)


@pytest.mark.parametrize("rule", ["cwtm", "multikrum"])
def test_single_swapped_leaf_through_hier_stage(rule):
    """A tree of ONE minor-swapped 3-D leaf under the hierarchical stage
    (s > 1): the stage flattens the leaf views to one (n, D) buffer, so
    the aggregate is one (D,) vector in row-major order — it must rebuild
    the leaf as it is, not be taken for the leaf's own (R, C) aggregate."""
    shape = (5, 200, 50)
    assert tiling.minor_swapped(shape) and tiling.leaf_view_fits(5)
    tree = {"w": jnp.asarray(
        np.random.default_rng(7).normal(size=shape), jnp.float32)}
    key = jax.random.PRNGKey(2)

    def spec(backend):
        return AggregatorSpec(rule=rule, f=1, pre="nnm", hier=True,
                              bucket_size=2, backend=backend)

    got = robust_lib.robust_aggregate(tree, spec("pallas"), key=key)
    assert kdispatch.last_dispatch().backend == "pallas"
    ref = robust_lib.robust_aggregate(tree, spec("xla"), key=key)
    _assert_trees_close(got, ref, rtol=1e-5, atol=1e-5, err_msg=rule)
