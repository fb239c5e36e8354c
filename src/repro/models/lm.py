"""Decoder language models: dense / MoE / VLM / RWKV6 / Zamba2-hybrid.

One assembly with per-family blocks, scan-over-layers (compile time is
independent of depth), a unified ``loss / forward / decode_step`` API, and
ParamDesc trees as the single source of truth for shapes + sharding.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, pad_to
from repro.models import attention, common, mlp, moe, rwkv, ssm
from repro.models.common import ParamDesc, constrain, rms_norm

Array = jax.Array
PyTree = Any


def _padded_vocab(cfg: ModelConfig) -> int:
    return pad_to(cfg.vocab_size, 128)


def _by_period(tree: PyTree, period: int) -> PyTree:
    """Layer-stacked leaves (L, ...) -> (L / period, period, ...)."""
    return jax.tree_util.tree_map(
        lambda a: a.reshape((a.shape[0] // period, period) + a.shape[1:]), tree)


def _norm_desc(cfg: ModelConfig, layers: int, n: int = 1):
    L = (layers,) if layers else ()
    lax = ("layers",) if layers else ()
    return {f"ln{i}": ParamDesc(L + (cfg.d_model,), cfg.dtype,
                                lax + ("embed",), "ones") for i in range(n)}


class DecoderLM:
    """Decoder-only LM for families: dense, moe, vlm, ssm, hybrid."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- parameters ---------------------------------------------------------

    def param_descs(self) -> PyTree:
        cfg = self.cfg
        d, L = cfg.d_model, cfg.num_layers
        pv = _padded_vocab(cfg)
        tree: dict = {
            "embed": ParamDesc((pv, d), cfg.dtype, ("vocab", "embed"), "embed"),
            "final_norm": ParamDesc((d,), cfg.dtype, ("embed",), "ones"),
        }
        if not cfg.tie_embeddings:
            tree["lm_head"] = ParamDesc((d, pv), cfg.dtype, ("embed", "vocab"))

        fam = cfg.family
        if fam in ("dense", "moe", "vlm"):
            blocks = {"attn": attention.attn_params(cfg, L), **_norm_desc(cfg, L, 2)}
            if fam == "moe":
                blocks["moe"] = moe.moe_params(cfg, L)
            else:
                blocks["mlp"] = mlp.swiglu_params(cfg, L)
            tree["blocks"] = blocks
        elif fam == "ssm":          # rwkv6
            tree["blocks"] = {"rwkv": rwkv.rwkv_params(cfg, L),
                              **_norm_desc(cfg, L, 2)}
        elif fam == "hybrid":       # zamba2
            assert L % cfg.attn_every == 0, (L, cfg.attn_every)
            tree["blocks"] = {"ssm": ssm.ssm_params(cfg, L),
                              **_norm_desc(cfg, L, 1)}
            shared_cfg = cfg
            tree["shared"] = {
                "attn": attention.attn_params(shared_cfg, 0),
                "mlp": mlp.swiglu_params(shared_cfg, 0),
                **_norm_desc(cfg, 0, 2),
            }
        else:
            raise ValueError(fam)

        if fam == "vlm":
            tree["projector"] = {
                "w1": ParamDesc((cfg.vision_dim, d), cfg.dtype, (None, "embed")),
                "w2": ParamDesc((d, d), cfg.dtype, ("embed", "embed")),
                "ln": ParamDesc((cfg.vision_dim,), cfg.dtype, (None,), "ones"),
            }
        return tree

    def init(self, key: Array) -> PyTree:
        return common.materialize(self.param_descs(), key)

    # -- embedding / head ---------------------------------------------------

    def _embed_tokens(self, params, tokens: Array) -> Array:
        emb = params["embed"][tokens]
        return constrain(emb, "batch", None, None)

    def _embed(self, params, batch: dict) -> Array:
        cfg = self.cfg
        x = self._embed_tokens(params, batch["tokens"])
        if cfg.family == "vlm":
            pr = params["projector"]
            p = rms_norm(batch["patches"].astype(cfg.dtype), pr["ln"], cfg.norm_eps)
            p = jax.nn.gelu(p @ pr["w1"]) @ pr["w2"]
            x = jnp.concatenate([p.astype(x.dtype), x], axis=1)
        return x

    def _logits(self, params, x: Array) -> Array:
        cfg = self.cfg
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = (x @ head).astype(jnp.float32)
        return constrain(logits, "batch", None, "vocab")

    # -- forward ------------------------------------------------------------

    def _sp(self, x: Array) -> Array:
        """Sequence-parallel residual-stream constraint (DESIGN.md §3)."""
        ctx = common.get_mesh_axes()
        if ctx is not None and ctx.seq_par:
            return constrain(x, "batch", "seq_model", None)
        return x

    def _maybe_remat(self, fn):
        return jax.checkpoint(fn) if self.cfg.remat else fn

    def _run_blocks(self, params, x: Array) -> tuple[Array, Array, dict]:
        """(hidden states, aux loss, the expert layers' routing counts:
        empty where the family has none)."""
        cfg = self.cfg
        fam = cfg.family
        aux0 = jnp.zeros((), jnp.float32)

        if fam in ("dense", "moe", "vlm"):
            def make_block(kind):
                def block(h, p):
                    h = self._sp(h)
                    a = attention.attention(
                        p["attn"], rms_norm(h, p["ln0"], cfg.norm_eps), cfg,
                        kind=kind)
                    h = h + a
                    h = self._sp(h)
                    if fam == "moe":
                        f, aux_l, stats = moe.moe_block(
                            p["moe"], rms_norm(h, p["ln1"], cfg.norm_eps), cfg)
                    else:
                        f = mlp.swiglu(p["mlp"], rms_norm(h, p["ln1"], cfg.norm_eps))
                        aux_l, stats = jnp.zeros((), jnp.float32), {}
                    return self._sp(h + f), aux_l, stats
                return self._maybe_remat(block)

            def apply(carry, block, p):
                h, aux, stats = carry
                h, aux_l, stats_l = block(h, p)
                if stats:
                    stats_l = moe.merge_stats(stats, stats_l)
                return h, aux + aux_l, stats_l

            stats0 = moe.zero_stats() if fam == "moe" else {}
            if cfg.layer_types:
                # Scan over periods; each period's layers unrolled with
                # their own window and rotary table.
                blocks = [make_block(kind) for kind in cfg.layer_types]
                xs = _by_period(params["blocks"], len(blocks))

                def body(carry, pg):
                    for i, block in enumerate(blocks):
                        carry = apply(carry, block,
                                      jax.tree_util.tree_map(lambda a: a[i], pg))
                    return carry, None
            else:
                block, xs = make_block(None), params["blocks"]

                def body(carry, p):
                    return apply(carry, block, p), None
            (x, aux, stats), _ = jax.lax.scan(body, (x, aux0, stats0), xs,
                                              unroll=cfg.scan_unroll)
            return x, aux, stats

        if fam == "ssm":
            def block(h, p):
                h = self._sp(h)
                h = h + rwkv.time_mix(p["rwkv"], rms_norm(h, p["ln0"], cfg.norm_eps), cfg)
                h = self._sp(h)
                h = h + rwkv.channel_mix(p["rwkv"], rms_norm(h, p["ln1"], cfg.norm_eps), cfg)
                return self._sp(h)
            block = self._maybe_remat(block)

            def body(carry, p):
                h, aux = carry
                return (block(h, p), aux), None
            (x, aux), _ = jax.lax.scan(body, (x, aux0), params["blocks"],
                                       unroll=cfg.scan_unroll)
            return x, aux, {}

        if fam == "hybrid":
            k = cfg.attn_every
            groups = cfg.num_layers // k
            stacked = jax.tree_util.tree_map(
                lambda a: a.reshape((groups, k) + a.shape[1:]), params["blocks"])
            shared = params["shared"]

            def mamba_block(h, p):
                h = self._sp(h)
                return h + ssm.ssm_block(p["ssm"], rms_norm(h, p["ln0"], cfg.norm_eps), cfg)
            mamba_block = self._maybe_remat(mamba_block)

            def shared_block(h):
                h = self._sp(h)
                a = attention.attention(shared["attn"],
                                        rms_norm(h, shared["ln0"], cfg.norm_eps), cfg)
                h = h + a
                h = self._sp(h)
                return h + mlp.swiglu(shared["mlp"], rms_norm(h, shared["ln1"], cfg.norm_eps))
            shared_block = self._maybe_remat(shared_block)

            def inner(h, p):
                return mamba_block(h, p), None

            def outer(carry, pg):
                h, aux = carry
                h, _ = jax.lax.scan(inner, h, pg, unroll=cfg.scan_unroll)
                h = shared_block(h)
                return (h, aux), None

            (x, aux), _ = jax.lax.scan(outer, (x, aux0), stacked,
                                       unroll=cfg.scan_unroll)
            return x, aux, {}

        raise ValueError(fam)

    def forward(self, params, batch: dict) -> Array:
        """Full-sequence logits (prefill path)."""
        x = self._embed(params, batch)
        x, _, _ = self._run_blocks(params, x)
        return self._logits(params, x)

    def loss(self, params, batch: dict) -> tuple[Array, dict]:
        """Next-token CE on text positions (+ MoE aux); the metrics carry
        the expert layers' routing counts (``moe.moe_block``)."""
        cfg = self.cfg
        x = self._embed(params, batch)
        x, aux, stats = self._run_blocks(params, x)
        if cfg.family == "vlm":
            x = x[:, cfg.num_patches:]          # text positions only
        logits = self._logits(params, x)
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        ce = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        total = ce + aux
        return total, {"ce": ce, "aux": aux, **stats}

    # -- decode -------------------------------------------------------------

    def cache_descs(self, batch: int, max_seq: int) -> PyTree:
        cfg = self.cfg
        fam = cfg.family
        if fam in ("dense", "moe", "vlm"):
            return attention.cache_desc(cfg, cfg.num_layers, batch, max_seq)
        if fam == "ssm":
            return rwkv.rwkv_cache_desc(cfg, cfg.num_layers, batch)
        if fam == "hybrid":
            groups = cfg.num_layers // cfg.attn_every
            return {
                "ssm": ssm.ssm_cache_desc(cfg, cfg.num_layers, batch),
                "attn": attention.cache_desc(cfg, groups, batch, max_seq),
            }
        raise ValueError(fam)

    def init_cache(self, batch: int, max_seq: int, key=None) -> PyTree:
        return common.materialize(self.cache_descs(batch, max_seq),
                                  key or jax.random.PRNGKey(0))

    def decode_step(self, params, cache: PyTree, tokens: Array, pos: Array
                    ) -> tuple[Array, PyTree]:
        """One decode step.  tokens: (B, 1) int32; pos: scalar int32.
        Returns (logits (B, 1, V), new cache)."""
        cfg = self.cfg
        fam = cfg.family
        x = self._embed_tokens(params, tokens)

        if fam in ("dense", "moe", "vlm"):
            def layer(h, inp, kind=None):
                p, ck, cv = inp
                a, ck2, cv2 = attention.decode_attention(
                    p["attn"], rms_norm(h, p["ln0"], cfg.norm_eps), ck, cv, pos, cfg,
                    kind=kind)
                h = h + a
                if fam == "moe":
                    f, _, _ = moe.moe_block(p["moe"], rms_norm(h, p["ln1"], cfg.norm_eps), cfg)
                else:
                    f = mlp.swiglu(p["mlp"], rms_norm(h, p["ln1"], cfg.norm_eps))
                return h + f, (ck2, cv2)
            xs = (params["blocks"], cache["k"], cache["v"])
            if not cfg.layer_types:
                x, (k2, v2) = jax.lax.scan(layer, x, xs)
                return self._logits(params, x), {"k": k2, "v": v2}

            def body(h, inp):
                out = []
                for i, kind in enumerate(cfg.layer_types):
                    h, kv = layer(h, jax.tree_util.tree_map(lambda a: a[i], inp),
                                  kind)
                    out.append(kv)
                return h, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *out)
            x, (k2, v2) = jax.lax.scan(body, x, _by_period(xs, len(cfg.layer_types)))
            k2, v2 = (a.reshape((-1,) + a.shape[2:]) for a in (k2, v2))
            return self._logits(params, x), {"k": k2, "v": v2}

        if fam == "ssm":
            def body(h, inp):
                p, st, tsh, csh = inp
                y, st2, tsh2 = rwkv.time_mix_decode(
                    p["rwkv"], rms_norm(h, p["ln0"], cfg.norm_eps), st, tsh, cfg)
                h = h + y
                y, csh2 = rwkv.channel_mix_decode(
                    p["rwkv"], rms_norm(h, p["ln1"], cfg.norm_eps), csh, cfg)
                return h + y, (st2, tsh2, csh2)
            x, (st, tsh, csh) = jax.lax.scan(
                body, x, (params["blocks"], cache["state"], cache["tshift"],
                          cache["cshift"]))
            return self._logits(params, x), {"state": st, "tshift": tsh, "cshift": csh}

        if fam == "hybrid":
            k = cfg.attn_every
            groups = cfg.num_layers // k
            stacked = jax.tree_util.tree_map(
                lambda a: a.reshape((groups, k) + a.shape[1:]), params["blocks"])
            sc = cache["ssm"]
            sstate = sc["state"].reshape((groups, k) + sc["state"].shape[1:])
            sconv = sc["conv"].reshape((groups, k) + sc["conv"].shape[1:])
            shared = params["shared"]

            def inner(h, inp):
                p, st, cv = inp
                y, st2, cv2 = ssm.ssm_decode_step(
                    p["ssm"], rms_norm(h, p["ln0"], cfg.norm_eps), st, cv, cfg)
                return h + y, (st2, cv2)

            def outer(h, inp):
                pg, stg, cvg, ck, cv = inp
                h, (st2, cv2) = jax.lax.scan(inner, h, (pg, stg, cvg))
                a, ck2, cv2a = attention.decode_attention(
                    shared["attn"], rms_norm(h, shared["ln0"], cfg.norm_eps),
                    ck, cv, pos, cfg)
                h = h + a
                h = h + mlp.swiglu(shared["mlp"], rms_norm(h, shared["ln1"], cfg.norm_eps))
                return h, (st2, cv2, ck2, cv2a)

            ac = cache["attn"]
            x, (st, cv_s, ck, cv) = jax.lax.scan(
                outer, x, (stacked, sstate, sconv, ac["k"], ac["v"]))
            new_cache = {
                "ssm": {"state": st.reshape(sc["state"].shape),
                        "conv": cv_s.reshape(sc["conv"].shape)},
                "attn": {"k": ck, "v": cv},
            }
            return self._logits(params, x), new_cache

        raise ValueError(fam)
