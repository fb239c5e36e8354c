"""The expert layer and Mellum2's layer types against the plain reference.

VERIFIES
* Mellum2's loss and gradients, at a small size on seeded random weights,
  equal the benchmark's plain float32 reference (``bench/configs/
  mellum2-12b-a2.5b.py``): a window shorter than the row on the sliding
  layers, YaRN on the full layer, 2 of 8 experts held;
* the SHARE test: summed over the E / H shares of the experts, the expert
  layer's partial outputs equal the uncut reference's whole layer, with
  attention and the router counted once;
* NO DROPS: a router that sends every token to one expert loses no token,
  in a layer that holds every expert and in one that holds a share;
* the routing counts reach the train step's metrics, and Mellum2's
  expert leaves take the robust per-worker path (no FSDP mean-gradient);
* the configs: published widths, and the chip share's 340,349,184
  parameters.
"""
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import chip_config, get_config, reduced_config
from repro.core import AggregatorSpec
from repro.models import build_model, moe
from repro.optim import sgd
from repro.optim.schedules import constant
from repro.training import (ByzantineConfig, TrainerConfig, build_train_step,
                            init_state)

BENCH = Path(__file__).resolve().parents[1] / "bench"
ARCH = "mellum2-12b-a2.5b"


@pytest.fixture(scope="module")
def ref():
    """The configuration's plain reference and its layer math."""
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    from harness import ref_layers
    from harness.cells import load_module
    return load_module(BENCH / "configs" / f"{ARCH}.py"), ref_layers


def _small(**kw):
    base = dict(num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
                head_dim=16, d_ff=32, vocab_size=256, num_experts=8,
                experts_per_token=3, experts_held=2, sliding_window=6,
                dtype=jnp.float32)
    return get_config(ARCH).replace(**dict(base, **kw))


def _sizes(cfg) -> dict:
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _by_path(tree) -> dict:
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _batch(cfg, rows=2, seq=16, seed=1):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (rows, seq), 0,
                             cfg.vocab_size)
    return {"tokens": tok, "labels": jnp.roll(tok, -1, axis=1)}


def test_mellum_loss_and_gradients_match_reference(ref):
    mod, L = ref
    cfg = _small()
    assert cfg.sliding_window < 16 and cfg.layer_types[-1] == "full"
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    flat = _by_path(params)
    specs = mod.param_specs(_sizes(cfg))
    assert {k: s for k, (s, _) in specs.items()} == \
        {k: v.shape for k, v in flat.items()}
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda p: model.loss(p, batch)[0])(params)
        lr, gr = jax.value_and_grad(
            lambda p: mod.loss(p, batch, L.matmul("fp32"), _sizes(cfg)))(flat)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    for k, g in _by_path(gp).items():
        want = np.asarray(gr[k])
        np.testing.assert_allclose(np.asarray(g), want, rtol=0,
                                   atol=2e-5 * np.abs(want).max(), err_msg=k)


def test_window_and_yarn_change_the_loss(ref):
    """The comparison above is not blind to either mechanism: without the
    window, or without YaRN, the reference's loss moves."""
    mod, L = ref
    cfg = _small()
    flat = _by_path(build_model(cfg).init(jax.random.PRNGKey(0)))
    batch = _batch(cfg)
    with jax.default_matmul_precision("highest"):
        base = float(mod.loss(flat, batch, L.matmul("fp32"), _sizes(cfg)))
        for other in (cfg.replace(sliding_window=1024),
                      cfg.replace(yarn_factor=0.0)):
            got = float(mod.loss(flat, batch, L.matmul("fp32"),
                                 _sizes(other)))
            assert abs(got - base) > 1e-4 * abs(base)


def _layer_weights(cfg, seed=3):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    e, d, ff = cfg.num_experts, cfg.d_model, cfg.d_ff
    return {"router": jax.random.normal(k[0], (d, e)) / math.sqrt(d),
            "wg": jax.random.normal(k[1], (e, d, ff)) / math.sqrt(d),
            "wi": jax.random.normal(k[2], (e, d, ff)) / math.sqrt(d),
            "wo": jax.random.normal(k[3], (e, ff, d)) / math.sqrt(ff)}


def _share(w, cfg, j):
    """Share j of the experts as the program holds it: its H experts are
    the layer's first, so the router's columns turn by j * H."""
    h = cfg.experts_held
    cols = np.roll(np.arange(cfg.num_experts), -j * h)
    return {"router": w["router"][:, cols],
            **{n: w[n][j * h:(j + 1) * h] for n in ("wg", "wi", "wo")}}


def test_expert_shares_sum_to_the_uncut_layer(ref):
    mod, L = ref
    cfg = _small()
    e, h = cfg.num_experts, cfg.experts_held
    w = _layer_weights(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))
    attn = build_model(cfg).init(jax.random.PRNGKey(5))["blocks"]["attn"]
    attn = jax.tree_util.tree_map(lambda a: a[-1], attn)
    from repro.models import attention
    with jax.default_matmul_precision("highest"):
        a = attention.attention(attn, x, cfg, kind="full")   # counted once
        whole, aux_whole = mod.experts(L.matmul("fp32"), x + a, w,
                                       _sizes(cfg.replace(experts_held=0)))
        parts, auxes, pairs = 0.0, [], 0
        for j in range(e // h):
            out, aux, stats = moe.moe_block(_share(w, cfg, j), x + a, cfg)
            parts, pairs = parts + out, pairs + int(stats["routed_pairs"])
            auxes.append(float(aux))
    np.testing.assert_allclose(np.asarray(x + a + parts),
                               np.asarray(x + a + whole), rtol=0, atol=1e-5)
    np.testing.assert_allclose(auxes, float(aux_whole), rtol=1e-6)
    assert pairs == 2 * 16 * cfg.experts_per_token


@pytest.mark.parametrize("held", [0, 2], ids=["all_held", "share"])
def test_router_to_one_expert_drops_nothing(ref, held):
    """Every token's first choice is expert 0: its load is every token,
    far past any capacity a dispatch buffer would size for 1/E of them."""
    mod, L = ref
    cfg = _small(num_experts=4, experts_per_token=2, experts_held=held)
    w = _layer_weights(cfg)
    w["router"] = w["router"].at[:, 0].set(5.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (2, 16,
                                                         cfg.d_model)))
    with jax.default_matmul_precision("highest"):
        got, _, stats = moe.moe_block(
            {k: v[: cfg.held_experts] if k != "router" else v
             for k, v in w.items()}, x, cfg)
        want, _ = mod.experts(L.matmul("fp32"), x, w, _sizes(cfg))
    assert int(stats["expert_load_max"]) == 32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5)


def test_routing_counts_reach_the_step_metrics():
    cfg = reduced_config(ARCH)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    tcfg = TrainerConfig(algorithm="dshb",
                         agg=AggregatorSpec(rule="cwtm", f=1, pre="nnm"),
                         byz=ByzantineConfig(f=1, attack="alie"))
    opt = sgd(clip=1.0)
    step = jax.jit(build_train_step(model.loss, opt, tcfg, constant(1e-2)))
    state = init_state(params, opt, 4, tcfg)
    # Every leaf, the experts' included, has a per-worker momentum row.
    assert len(state["momentum"]) == len(jax.tree_util.tree_leaves(params))
    tok = jax.random.randint(jax.random.PRNGKey(2), (4, 2, 32), 0,
                             cfg.vocab_size)
    _, m = step(state, {"tokens": tok, "labels": tok}, jax.random.PRNGKey(3))
    pairs = int(m["routed_pairs"])
    # 4 workers x 64 tokens x k=2 choices of 4 experts, 2 held, 4 layers.
    assert 0 < pairs <= 4 * 64 * 2 * 4
    assert 0 <= int(m["expert_load_min"]) <= int(m["expert_load_max"]) <= 64


def test_mellum_experts_take_the_robust_path():
    from repro.launch.launch_config import fsdp_keys_for
    assert TrainerConfig().fsdp_keys == ()
    assert fsdp_keys_for(get_config(ARCH)) == ()
    assert fsdp_keys_for(chip_config(ARCH)) == ()


def test_mellum_configs_widths_and_share():
    cfg = get_config(ARCH)
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.num_experts, cfg.experts_per_token,
            cfg.sliding_window) == (2304, 32, 4, 128, 896, 64, 8, 1024)
    assert cfg.layer_types == ("sliding",) * 3 + ("full",)
    assert cfg.yarn_factor == 16 and cfg.yarn_original_max_position == 8192
    assert cfg.held_experts == 64 and cfg.num_layers == 28
    share = chip_config(ARCH)
    assert (share.num_layers, share.experts_held, share.vocab_size) == \
        (4, 8, 12288)
    shapes = jax.eval_shape(build_model(share).init, jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape) for x in
               jax.tree_util.tree_leaves(shapes)) == 340_349_184
