"""Plain float32 reference of SmolLM-360M (llama architecture): RMSNorm,
grouped-query attention with rotary positions, SwiGLU, tied embeddings.

Weights are laid out under the leaf paths of the configuration as it is
run, so that the comparison can go leaf by leaf.
"""
from __future__ import annotations

import jax

from harness import ref_layers as L

BLOCK = "['blocks']"


def param_specs(s: dict) -> dict:
    """{leaf path: (shape, init kind)}."""
    d, n, ff, hd = s["d_model"], s["num_layers"], s["d_ff"], s["head_dim"]
    q, kv = s["num_heads"] * hd, s["num_kv_heads"] * hd
    v = L.pad_vocab(s["vocab_size"])
    out = {
        "['embed']": ((v, d), "embed"),
        "['final_norm']": ((d,), "ones"),
        f"{BLOCK}['attn']['wq']": ((n, d, q), "normal"),
        f"{BLOCK}['attn']['wk']": ((n, d, kv), "normal"),
        f"{BLOCK}['attn']['wv']": ((n, d, kv), "normal"),
        f"{BLOCK}['attn']['wo']": ((n, q, d), "normal"),
        f"{BLOCK}['ln0']": ((n, d), "ones"),
        f"{BLOCK}['ln1']": ((n, d), "ones"),
        f"{BLOCK}['mlp']['wg']": ((n, d, ff), "normal"),
        f"{BLOCK}['mlp']['wi']": ((n, d, ff), "normal"),
        f"{BLOCK}['mlp']['wo']": ((n, ff, d), "normal"),
    }
    if not s["tie_embeddings"]:
        out["['lm_head']"] = ((d, v), "normal")
    return out


def loss(p: dict, batch: dict, mm: L.MatMul, s: dict) -> jax.Array:
    """Mean next-token cross-entropy of one worker's (B, S) batch."""
    eps, hd = s["norm_eps"], s["head_dim"]
    x = p["['embed']"][batch["tokens"]]
    b, t, _ = x.shape
    names = [k for k in p if k.startswith(BLOCK)]
    layers = {k: p[k] for k in names}

    def block(h, w):
        a = L.rms_norm(h, w[f"{BLOCK}['ln0']"], eps)
        q = mm("bsd,dh->bsh", a, w[f"{BLOCK}['attn']['wq']"])
        k = mm("bsd,dh->bsh", a, w[f"{BLOCK}['attn']['wk']"])
        v = mm("bsd,dh->bsh", a, w[f"{BLOCK}['attn']['wv']"])
        q = L.rope(q.reshape(b, t, -1, hd), s["rope_theta"])
        k = L.rope(k.reshape(b, t, -1, hd), s["rope_theta"])
        o = L.attention(mm, q, k, v.reshape(b, t, -1, hd), causal=True)
        h = h + mm("bsh,hd->bsd", o, w[f"{BLOCK}['attn']['wo']"])
        a = L.rms_norm(h, w[f"{BLOCK}['ln1']"], eps)
        g = jax.nn.silu(mm("bsd,df->bsf", a, w[f"{BLOCK}['mlp']['wg']"]))
        u = mm("bsd,df->bsf", a, w[f"{BLOCK}['mlp']['wi']"])
        return h + mm("bsf,fd->bsd", g * u, w[f"{BLOCK}['mlp']['wo']"]), None

    x, _ = jax.lax.scan(block, x, layers)
    x = L.rms_norm(x, p["['final_norm']"], eps)
    if s["tie_embeddings"]:
        logits = mm("bsd,vd->bsv", x, p["['embed']"])
    else:
        logits = mm("bsd,dv->bsv", x, p["['lm_head']"])
    return L.cross_entropy(logits, batch["labels"])


def matmul_positions(s: dict, traffic: dict) -> dict:
    """{leaf path: input positions per row that the leaf multiplies}: the
    weights that take part in a matrix product, each once per token.  The
    embedding is a lookup, and a product only as the tied head."""
    t = int(traffic["seq"])
    out = {k: t for k, (shape, kind) in param_specs(s).items()
           if kind == "normal"}
    if s["tie_embeddings"]:
        out["['embed']"] = t
    return out


def attention_flops_per_row(s: dict, traffic: dict) -> float:
    """Forward FLOPs of the score and value products of one row, causal
    half only."""
    t = int(traffic["seq"])
    width = s["num_heads"] * s["head_dim"]
    return s["num_layers"] * 4.0 * width * t * t / 2
