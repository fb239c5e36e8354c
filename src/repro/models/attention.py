"""GQA attention: training (causal / bidirectional / sliding-window) and
cached single-token decode.

Sharding: q heads shard over the model axis (padded to the mesh per
``common.pad_heads``); kv heads shard only when divisible, otherwise the
(small, GQA) kv tensors replicate and are repeated to the q-head count so
the group structure never crosses shard boundaries (DESIGN.md §3).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.common import ParamDesc, apply_rope, constrain

Array = jax.Array
NEG_INF = -1e30


def resolved_heads(cfg: ModelConfig) -> tuple[int, int]:
    ctx = common.get_mesh_axes()
    par = ctx.model_par if ctx else 1
    pad_kv = bool(ctx and ctx.pad_kv_to_mesh)
    hq, hkv, _, _ = common.pad_heads(cfg.num_heads, cfg.num_kv_heads, par,
                                     pad_kv=pad_kv)
    return hq, hkv


def attn_params(cfg: ModelConfig, layers: int) -> dict:
    hq, hkv = resolved_heads(cfg)
    d, hd = cfg.d_model, cfg.head_dim
    L = (layers,) if layers else ()
    lax = ("layers",) if layers else ()
    p = {
        "wq": ParamDesc(L + (d, hq * hd), cfg.dtype, lax + ("embed", "heads")),
        "wk": ParamDesc(L + (d, hkv * hd), cfg.dtype, lax + ("embed", "kv")),
        "wv": ParamDesc(L + (d, hkv * hd), cfg.dtype, lax + ("embed", "kv")),
        "wo": ParamDesc(L + (hq * hd, d), cfg.dtype, lax + ("heads", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = ParamDesc(L + (hq * hd,), cfg.dtype, lax + ("heads",), "zeros")
        p["bk"] = ParamDesc(L + (hkv * hd,), cfg.dtype, lax + ("kv",), "zeros")
        p["bv"] = ParamDesc(L + (hkv * hd,), cfg.dtype, lax + ("kv",), "zeros")
    return p


def _project_qkv(p: dict, x: Array, cfg: ModelConfig):
    hq, hkv = resolved_heads(cfg)
    hd = cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    b, s = x.shape[:2]
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    return q, k, v


def _repeat_kv(k: Array, hq: int) -> Array:
    hkv = k.shape[-2]
    if hkv == hq:
        return k
    return jnp.repeat(k, hq // hkv, axis=-2)


def layer_window(cfg: ModelConfig, kind: Optional[str]) -> Optional[int]:
    """The causal window of a layer of type ``kind`` (None: untyped, the
    config's ``sliding_window``); None for no window."""
    return None if kind == "full" else cfg.sliding_window


def _rope(x: Array, positions: Array, cfg: ModelConfig,
          kind: Optional[str]) -> Array:
    freqs, scale = common.rope_table(cfg, kind)
    return apply_rope(x, positions, cfg.rope_theta, freqs=freqs, scale=scale)


def attention(p: dict, x: Array, cfg: ModelConfig, *,
              causal: bool = True, positions: Optional[Array] = None,
              use_rope: bool = True,
              kv_override: Optional[tuple[Array, Array]] = None,
              kind: Optional[str] = None) -> Array:
    """Full-sequence attention.  x: (B, S, d) -> (B, S, d).

    ``kv_override`` supplies external (k, v) head tensors for cross
    attention (whisper decoder); causal/sliding masks then do not apply.
    ``kind`` is the layer's type in ``cfg.layer_types`` ("sliding" or
    "full"): its window and its rotary table.
    """
    b, s, _ = x.shape
    hq, _ = resolved_heads(cfg)
    hd = cfg.head_dim
    if positions is None:
        positions = jnp.arange(s)[None, :]

    q, k, v = _project_qkv(p, x, cfg)
    cross = kv_override is not None
    if cross:
        k, v = kv_override
    elif use_rope:
        q = _rope(q, positions, cfg, kind)
        k = _rope(k, positions, cfg, kind)
    k = _repeat_kv(k, hq)
    v = _repeat_kv(v, hq)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "heads", None)

    scale = hd ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if not cross:
        qi = jnp.arange(s)[:, None]
        kj = jnp.arange(s)[None, :]
        mask = qi >= kj if causal else jnp.ones((s, s), bool)
        window = layer_window(cfg, kind)
        if window and causal:
            mask = mask & (qi - kj < window)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    out = constrain(out, "batch", None, "heads", None)
    return out.reshape(b, s, hq * hd) @ p["wo"]


# ---------------------------------------------------------------------------
# KV-cache decode.
# ---------------------------------------------------------------------------

def cache_desc(cfg: ModelConfig, layers: int, batch: int, max_seq: int) -> dict:
    """KV-cache sharding policy (DESIGN.md §3):

    * batch dim shards over the data axes when batch > 1;
    * kv-head dim shards over model when divisible;
    * otherwise the model axis shards the cache *sequence* dim instead
      (flash-decode style: GSPMD resolves the softmax over the sharded
      seq with partial-reduce collectives);
    * batch == 1 long-context decode additionally spreads seq over the
      data axes (its only use for a single request).
    Sliding-window archs cache only the window (ring buffer); where layer
    types differ, every layer caches the whole span.
    """
    ctx = common.get_mesh_axes()
    kv_sharded = bool(ctx and ctx.shard_kv and ctx.model_par > 1)
    span = min(max_seq, cfg.sliding_window) \
        if cfg.sliding_window and not cfg.layer_types else max_seq
    if batch == 1:
        b_axis = None
        seq_axis = "seq_shard" if kv_sharded else "seq_both"
        if span <= 8192:             # window caches are small: replicate seq
            seq_axis = None
    else:
        b_axis = "batch"
        seq_axis = None if kv_sharded else "seq_model"
        if span <= 8192:
            seq_axis = None
    shape = (layers, batch, span, hkv_of(cfg), cfg.head_dim)
    axes = ("layers", b_axis, seq_axis, "kv" if kv_sharded else None, None)
    return {
        "k": ParamDesc(shape, cfg.dtype, axes, "zeros"),
        "v": ParamDesc(shape, cfg.dtype, axes, "zeros"),
    }


def hkv_of(cfg: ModelConfig) -> int:
    return resolved_heads(cfg)[1]


def decode_attention(p: dict, x: Array, cache_k: Array, cache_v: Array,
                     pos: Array, cfg: ModelConfig, *,
                     use_rope: bool = True,
                     kv_override: Optional[tuple[Array, Array]] = None,
                     kind: Optional[str] = None):
    """Single-token decode.  x: (B, 1, d); cache_{k,v}: (B, span, hkv, hd);
    pos: scalar current position.  Returns (out (B,1,d), new_k, new_v).
    A typed layer (``kind``) indexes its cache by position and masks its
    own window.
    """
    b = x.shape[0]
    hq, hkv = resolved_heads(cfg)
    hd = cfg.head_dim
    span = cache_k.shape[1]

    q, k, v = _project_qkv(p, x, cfg)
    if kv_override is not None:
        # Cross attention: static kv, cache untouched.
        ck, cv = kv_override
        valid = jnp.ones((ck.shape[1],), bool)
    else:
        if use_rope:
            posb = jnp.broadcast_to(pos, (b, 1))
            q = _rope(q, posb, cfg, kind)
            k = _rope(k, posb, cfg, kind)
        # Sliding-window caches are rings; full caches index by position.
        ring = cfg.sliding_window and kind is None
        window = layer_window(cfg, kind)
        slot = pos % span if ring else pos
        cache_k = cache_k.at[:, slot].set(k[:, 0])
        cache_v = cache_v.at[:, slot].set(v[:, 0])
        ck, cv = cache_k, cache_v
        idx = jnp.arange(span)
        valid = idx <= slot
        if ring:
            valid = valid | (pos >= span)   # ring full: every slot is live
        elif window:
            valid = valid & (pos - idx < window)

    scale = hd ** -0.5
    if cfg.gqa_einsum and ck.shape[-2] != hq:
        # Grouped GQA: contract q-head groups against the SHARED kv heads
        # directly — the repeated (B, S, Hq, hd) kv copy never materializes
        # (EXPERIMENTS.md §Perf, decode memory hillclimb).
        g = hq // ck.shape[-2]
        qg = q.reshape(b, 1, ck.shape[-2], g, hd)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ck,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(valid[None, None, None, None, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, cv)
        out = out.reshape(b, 1, hq * hd) @ p["wo"]
    else:
        ck = _repeat_kv(ck, hq)
        cv = _repeat_kv(cv, hq)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, ck,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, cv)
        out = out.reshape(b, 1, hq * hd) @ p["wo"]
    if kv_override is not None:
        return out, None, None
    return out, cache_k, cache_v
