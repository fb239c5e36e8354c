"""Per-architecture smoke tests: REDUCED variant of each assigned family,
one forward + one robust train step on CPU, asserting shapes + no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.core import AggregatorSpec
from repro.models import build_model
from repro.optim import sgd
from repro.optim.schedules import constant
from repro.training import ByzantineConfig, TrainerConfig, build_train_step, init_state

B, S, W = 2, 32, 4  # per-worker batch, seq, workers


def _batch(cfg, key, workers=None):
    shape = (workers, B, S) if workers else (B, S)
    tokens = jax.random.randint(key, shape, 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    lead = (workers, B) if workers else (B,)
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            key, lead + (cfg.num_patches, cfg.vision_dim))
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            key, lead + (cfg.encoder_seq, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The full config must carry the exact assigned hyperparameters."""
    cfg = get_config(arch)
    expect = {
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
        "codeqwen1.5-7b": (32, 4096, 32, 32, 13440, 92416),
        "qwen2-7b": (28, 3584, 28, 4, 18944, 152064),
        "smollm-360m": (32, 960, 15, 5, 2560, 49152),
        "minitron-8b": (32, 4096, 32, 8, 16384, 256000),
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "rwkv6-3b": (32, 2560, 40, 40, 8960, 65536),
        "mellum2-12b-a2.5b": (28, 2304, 32, 4, 896, 98304),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expect
    if arch == "arctic-480b":
        assert cfg.num_experts == 128 and cfg.experts_per_token == 2
        assert cfg.moe_dense_ff > 0
    if arch == "mixtral-8x22b":
        assert cfg.num_experts == 8 and cfg.sliding_window
    if arch == "zamba2-2.7b":
        assert cfg.ssm_state == 64 and cfg.attn_every > 0
    if arch == "rwkv6-3b":
        assert cfg.family == "ssm"
    if arch == "mellum2-12b-a2.5b":
        assert cfg.num_experts == 64 and cfg.experts_per_token == 8
        assert cfg.sliding_window == 1024 and cfg.yarn_factor == 16


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_forward_and_loss(arch):
    cfg = reduced_config(arch)
    assert cfg.num_layers <= max(2, len(cfg.layer_types)) and cfg.d_model <= 512
    if cfg.num_experts:
        assert cfg.num_experts <= 4
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    batch = _batch(cfg, key)
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), arch
    logits = model.forward(params, batch)
    assert logits.ndim == 3 and logits.shape[0] == B
    assert bool(jnp.isfinite(logits).all()), arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_robust_train_step(arch):
    """One full robust D-SHB step (NNM+CWTM, ALIE attack) per family."""
    cfg = reduced_config(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(1)
    params = model.init(key)
    tcfg = TrainerConfig(algorithm="dshb",
                         agg=AggregatorSpec(rule="cwtm", f=1, pre="nnm"),
                         byz=ByzantineConfig(f=1, attack="alie"))
    optimizer = sgd(clip=1.0)
    step_fn = jax.jit(build_train_step(model.loss, optimizer, tcfg,
                                       constant(1e-2)))
    state = init_state(params, optimizer, W, tcfg)
    batch = _batch(cfg, key, workers=W)
    state, metrics = step_fn(state, batch, key)
    assert bool(jnp.isfinite(metrics["loss"])), arch
    assert bool(jnp.isfinite(metrics["direction_norm"])), arch
    for leaf in jax.tree_util.tree_leaves(state["params"]):
        assert bool(jnp.isfinite(leaf).all()), arch
    # params actually moved
    moved = any(
        float(jnp.abs(a - b).max()) > 0
        for a, b in zip(jax.tree_util.tree_leaves(state["params"]),
                        jax.tree_util.tree_leaves(params)))
    assert moved, arch


@pytest.mark.parametrize("arch", ["qwen2-7b", "rwkv6-3b", "zamba2-2.7b",
                                  "whisper-base", "internvl2-2b",
                                  "mellum2-12b-a2.5b"])
def test_decode_matches_prefill(arch):
    """Incremental cached decode == full forward, per family (mellum2: the
    expert layer, a window shorter than the row and YaRN, per layer type)."""
    cfg = reduced_config(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(2)
    params = model.init(key)
    tokens = jax.random.randint(key, (B, 16), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.family == "vlm":
        # decode path has no patch prefix; compare text-only forward
        batch["patches"] = jnp.zeros((B, cfg.num_patches, cfg.vision_dim))
    if cfg.family == "encdec":
        frames = jax.random.normal(key, (B, cfg.encoder_seq, cfg.d_model))
        batch["frames"] = frames
    full = model.forward(params, batch)
    if cfg.family == "vlm":
        full = full[:, cfg.num_patches:]
        # decode_step embeds tokens only; patch prefix influences prefill —
        # use zero patches so the comparison is exact modulo the prefix.
        pytest.skip("vlm decode compares against text-only context; covered"
                    " by dedicated serving test")
    if cfg.family == "encdec":
        cache = model.prefill_cache(params, frames, B, 16)
    else:
        cache = model.init_cache(B, 16)
    step = jax.jit(model.decode_step)
    outs = []
    for t in range(16):
        lg, cache = step(params, cache, tokens[:, t:t + 1], jnp.int32(t))
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step_runs(arch):
    cfg = reduced_config(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(3)
    params = model.init(key)
    if cfg.family == "encdec":
        frames = jax.random.normal(key, (B, cfg.encoder_seq, cfg.d_model))
        cache = model.prefill_cache(params, frames, B, 8)
    else:
        cache = model.init_cache(B, 8)
    tok = jax.random.randint(key, (B, 1), 0, cfg.vocab_size)
    logits, cache2 = jax.jit(model.decode_step)(params, cache, tok,
                                                jnp.int32(0))
    assert logits.shape[:2] == (B, 1)
    assert bool(jnp.isfinite(logits).all()), arch
    assert jax.tree_util.tree_structure(cache2) == \
        jax.tree_util.tree_structure(cache)


def test_sliding_window_limits_attention():
    """Tokens beyond the window must not influence the output."""
    cfg = reduced_config("mixtral-8x22b").replace(sliding_window=4,
                                                  num_experts=0, family="dense")
    model = build_model(cfg)
    key = jax.random.PRNGKey(4)
    params = model.init(key)
    t1 = jax.random.randint(key, (1, 12), 0, cfg.vocab_size)
    t2 = t1.at[:, 0].set((t1[0, 0] + 1) % cfg.vocab_size)
    l1 = model.forward(params, {"tokens": t1})
    l2 = model.forward(params, {"tokens": t2})
    # position 11 attends to [8..11] only -> unchanged by token 0
    np.testing.assert_allclose(np.asarray(l1[:, -1]), np.asarray(l2[:, -1]),
                               rtol=1e-4, atol=1e-4)
    # position 2 is inside token 0's window -> must change
    assert float(jnp.abs(l1[:, 2] - l2[:, 2]).max()) > 1e-4
