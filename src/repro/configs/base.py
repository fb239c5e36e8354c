"""Architecture configuration schema + input-shape registry.

Every assigned architecture gets one module in this package exporting
``CONFIG``; the registry in ``repro.configs`` maps the public ``--arch`` ids
to them.  Shapes are the four assigned global input shapes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    qkv_bias: bool = False
    sliding_window: Optional[int] = None
    tie_embeddings: bool = False
    # --- MoE ---
    num_experts: int = 0             # experts the router scores
    experts_per_token: int = 2
    # Experts 0 .. experts_held - 1 are held and computed here (one
    # device's share under expert parallelism); 0 = all num_experts.
    experts_held: int = 0
    moe_dense_ff: int = 0            # parallel dense residual FFN (arctic)
    router_aux_weight: float = 0.01
    # --- layer types (mellum2) ---
    # A period of attention kinds, "sliding" (causal within
    # sliding_window) or "full", repeated over the depth; () = every layer
    # alike (sliding_window, if set, on all of them).
    layer_types: tuple = ()
    # YaRN rotary scaling on "full" layers (transformers' rope_type
    # "yarn"): the factor over the original context; 0 = none.
    yarn_factor: float = 0.0
    yarn_original_max_position: int = 0
    # --- SSM (mamba2 / rwkv6) ---
    ssm_state: int = 0               # N (mamba2 state) or unused for rwkv
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 64
    # --- hybrid (zamba2) ---
    attn_every: int = 0              # shared attn block cadence; 0 = never
    # --- encoder-decoder (whisper) ---
    encoder_layers: int = 0
    encoder_seq: int = 0             # frames after the (stubbed) conv frontend
    # --- VLM (internvl2) ---
    num_patches: int = 0
    vision_dim: int = 0
    # --- numerics / execution ---
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False              # jax.checkpoint around each block
    # Grouped-GQA decode (no materialized kv repeat): confirmed strict
    # win in §Perf (-21% memory, -99% collective on minitron decode_32k);
    # default ON.  The repeat path remains for A/B measurement.
    gqa_einsum: bool = True
    scan_unroll: int = 1             # lax.scan unroll for layer stacks
                                     # (dry-run cost probes unroll fully:
                                     # XLA cost analysis counts while-loop
                                     # bodies once — see launch/dryrun.py)
    source: str = ""                 # citation bracket from the assignment

    def __post_init__(self):
        # A configuration file gives layer_types as a JSON list.
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.layer_types:
            assert set(self.layer_types) <= {"sliding", "full"}, self.layer_types
            assert self.num_layers % len(self.layer_types) == 0, \
                (self.num_layers, self.layer_types)

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.num_experts

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    def supports_long_decode(self) -> bool:
        """Sub-quadratic decode: SSM/hybrid native; attention via SWA."""
        if self.family in ("ssm", "hybrid"):
            return True
        if self.family == "encdec":
            return False             # whisper: ≤448-token decode grammar
        return True                  # dense/moe/vlm via sliding_window override


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode


SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


def pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult
