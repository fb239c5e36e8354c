"""Architecture registry: public --arch ids -> ModelConfig."""
from __future__ import annotations

import importlib

from repro.configs.base import InputShape, ModelConfig, SHAPES

_ARCH_MODULES = {
    "arctic-480b": "repro.configs.arctic_480b",
    "mixtral-8x22b": "repro.configs.mixtral_8x22b",
    "internvl2-2b": "repro.configs.internvl2_2b",
    "codeqwen1.5-7b": "repro.configs.codeqwen15_7b",
    "qwen2-7b": "repro.configs.qwen2_7b",
    "smollm-360m": "repro.configs.smollm_360m",
    "minitron-8b": "repro.configs.minitron_8b",
    "zamba2-2.7b": "repro.configs.zamba2_2p7b",
    "whisper-base": "repro.configs.whisper_base",
    "rwkv6-3b": "repro.configs.rwkv6_3b",
    "mellum2-12b-a2.5b": "repro.configs.mellum2_12b_a2p5b",
}

ARCH_IDS = tuple(_ARCH_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise ValueError(f"unknown arch {arch!r}; known: {list(ARCH_IDS)}")
    return importlib.import_module(_ARCH_MODULES[arch]).CONFIG


def chip_config(arch: str) -> ModelConfig:
    """The config one chip runs at published widths: the arch's own, or,
    where the module states one (``CHIP_SHARE``), one chip's share of a
    deployment that splits the model over several."""
    cfg = get_config(arch)
    return getattr(importlib.import_module(_ARCH_MODULES[arch]),
                   "CHIP_SHARE", cfg)


def reduced_config(arch: str) -> ModelConfig:
    """CPU-smoke variant of the same family: <=2 layers (one period where
    layer types differ), d_model<=512, <=4 experts (2 held where the arch
    holds a share), tiny vocab.  Exercises every code path of the full
    arch."""
    cfg = get_config(arch)
    kw = dict(
        num_layers=2,
        d_model=min(cfg.d_model, 128),
        d_ff=min(cfg.d_ff, 256),
        vocab_size=min(cfg.vocab_size, 512),
        head_dim=32,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_kv_heads < cfg.num_heads else 4,
    )
    if cfg.num_experts:
        kw.update(num_experts=4, moe_dense_ff=64 if cfg.moe_dense_ff else 0)
    if cfg.sliding_window:
        kw.update(sliding_window=32)
    if cfg.layer_types:
        # One period; a window shorter than the smoke tests' 32-token rows.
        kw.update(num_layers=len(cfg.layer_types), sliding_window=8)
    if chip_config(arch).experts_held:
        kw.update(experts_per_token=2, experts_held=2)
    if cfg.family in ("ssm", "hybrid"):
        kw.update(ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_chunk=16)
    if cfg.family == "hybrid":
        kw.update(attn_every=1)
    if cfg.family == "encdec":
        kw.update(encoder_layers=2, encoder_seq=32)
    if cfg.family == "vlm":
        kw.update(num_patches=8, vision_dim=64)
    import jax.numpy as jnp
    kw.update(dtype=jnp.float32, name=cfg.name + "-reduced")
    return cfg.replace(**kw)


__all__ = ["ARCH_IDS", "get_config", "chip_config", "reduced_config", "InputShape",
           "ModelConfig", "SHAPES"]
