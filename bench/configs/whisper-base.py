"""Plain float32 reference of Whisper-base as it is run here: encoder over
precomputed frame embeddings (the convolutional front end is not part of
the configuration), sinusoidal positions on both sides, bidirectional
encoder self-attention, causal decoder self-attention, cross-attention,
GELU (tanh form) MLPs with biases, LayerNorm with biases, an untied head
over the vocabulary padded to 51968, and no attention biases.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import ref_layers as L

ENC, DEC = "['encoder']", "['decoder']"


def _ln(prefix: str, n: int, layers, d: int) -> dict:
    shape = (layers, d) if layers else (d,)
    out = {}
    for i in range(n):
        out[f"{prefix}['ln{i}_g']"] = (shape, "ones")
        out[f"{prefix}['ln{i}_b']"] = (shape, "zeros")
    return out


def _attn(prefix: str, layers: int, d: int, width: int) -> dict:
    return {f"{prefix}['wq']": ((layers, d, width), "normal"),
            f"{prefix}['wk']": ((layers, d, width), "normal"),
            f"{prefix}['wv']": ((layers, d, width), "normal"),
            f"{prefix}['wo']": ((layers, width, d), "normal")}


def _mlp(prefix: str, layers: int, d: int, ff: int) -> dict:
    return {f"{prefix}['wi']": ((layers, d, ff), "normal"),
            f"{prefix}['bi']": ((layers, ff), "zeros"),
            f"{prefix}['wo']": ((layers, ff, d), "normal"),
            f"{prefix}['bo']": ((layers, d), "zeros")}


def param_specs(s: dict) -> dict:
    """{leaf path: (shape, init kind)}."""
    d, ff = s["d_model"], s["d_ff"]
    width = s["num_heads"] * s["head_dim"]
    ne, nd = s["encoder_layers"], s["num_layers"]
    v = L.pad_vocab(s["vocab_size"])
    return {
        "['embed']": ((v, d), "embed"),
        "['lm_head']": ((d, v), "normal"),
        **_attn(f"{ENC}['attn']", ne, d, width),
        **_mlp(f"{ENC}['mlp']", ne, d, ff),
        **_ln(ENC, 2, ne, d),
        **_ln("['enc_norm']", 1, 0, d),
        **_attn(f"{DEC}['self_attn']", nd, d, width),
        **_attn(f"{DEC}['cross_attn']", nd, d, width),
        **_mlp(f"{DEC}['mlp']", nd, d, ff),
        **_ln(DEC, 3, nd, d),
        **_ln("['dec_norm']", 1, 0, d),
    }


def _heads(x, hd):
    return x.reshape(x.shape[0], x.shape[1], -1, hd)


def loss(p: dict, batch: dict, mm: L.MatMul, s: dict) -> jax.Array:
    """Mean next-token cross-entropy of one worker's batch: frames
    (B, F, d), tokens and labels (B, S)."""
    eps, hd = s["norm_eps"], s["head_dim"]

    def mlp(w, pre, x):
        h = jax.nn.gelu(mm("bsd,df->bsf", x, w[f"{pre}['mlp']['wi']"])
                        + w[f"{pre}['mlp']['bi']"], approximate=True)
        return mm("bsf,fd->bsd", h, w[f"{pre}['mlp']['wo']"]) \
            + w[f"{pre}['mlp']['bo']"]

    def ln(w, pre, i, x):
        return L.layer_norm(x, w[f"{pre}['ln{i}_g']"], w[f"{pre}['ln{i}_b']"],
                            eps)

    def proj(w, name, x):
        return _heads(mm("bsd,dh->bsh", x, w[name]), hd)

    x = batch["frames"].astype(jnp.float32)
    x = x + L.sinusoid(x.shape[1], x.shape[2])

    def enc_block(h, w):
        a = ln(w, ENC, 0, h)
        pre = f"{ENC}['attn']"
        o = L.attention(mm, proj(w, f"{pre}['wq']", a),
                        proj(w, f"{pre}['wk']", a),
                        proj(w, f"{pre}['wv']", a), causal=False)
        h = h + mm("bsh,hd->bsd", o, w[f"{pre}['wo']"])
        return h + mlp(w, ENC, ln(w, ENC, 1, h)), None

    enc, _ = jax.lax.scan(enc_block, x, {k: p[k] for k in p
                                         if k.startswith(ENC)})
    enc = ln(p, "['enc_norm']", 0, enc)

    y = p["['embed']"][batch["tokens"]]
    y = y + L.sinusoid(y.shape[1], y.shape[2])

    def dec_block(h, w):
        a = ln(w, DEC, 0, h)
        pre = f"{DEC}['self_attn']"
        o = L.attention(mm, proj(w, f"{pre}['wq']", a),
                        proj(w, f"{pre}['wk']", a),
                        proj(w, f"{pre}['wv']", a), causal=True)
        h = h + mm("bsh,hd->bsd", o, w[f"{pre}['wo']"])
        a = ln(w, DEC, 1, h)
        pre = f"{DEC}['cross_attn']"
        o = L.attention(mm, proj(w, f"{pre}['wq']", a),
                        proj(w, f"{pre}['wk']", enc),
                        proj(w, f"{pre}['wv']", enc), causal=False)
        h = h + mm("bsh,hd->bsd", o, w[f"{pre}['wo']"])
        return h + mlp(w, DEC, ln(w, DEC, 2, h)), None

    y, _ = jax.lax.scan(dec_block, y, {k: p[k] for k in p
                                       if k.startswith(DEC)})
    y = ln(p, "['dec_norm']", 0, y)
    logits = mm("bsd,dv->bsv", y, p["['lm_head']"])
    return L.cross_entropy(logits, batch["labels"])


def matmul_positions(s: dict, traffic: dict) -> dict:
    """{leaf path: input positions per row that the leaf multiplies}:
    encoder weights and the cross-attention keys and values take every
    frame, the other decoder weights and the head every token.  The
    embedding is a lookup."""
    frames, tokens = int(traffic["frames"]), int(traffic["seq"])
    out = {}
    for k, (shape, kind) in param_specs(s).items():
        if kind != "normal":
            continue
        on_frames = k.startswith(ENC) or k in (
            f"{DEC}['cross_attn']['wk']", f"{DEC}['cross_attn']['wv']")
        out[k] = frames if on_frames else tokens
    return out


def attention_flops_per_row(s: dict, traffic: dict) -> float:
    """Forward FLOPs of the score and value products of one row: the
    encoder's full square, the decoder's causal half, and the decoder's
    tokens against every frame."""
    frames, tokens = int(traffic["frames"]), int(traffic["seq"])
    width = s["num_heads"] * s["head_dim"]
    return 4.0 * width * (s["encoder_layers"] * frames * frames
                          + s["num_layers"] * (tokens * tokens / 2
                                               + tokens * frames))
