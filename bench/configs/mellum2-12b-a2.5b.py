"""Plain float32 reference of Mellum2-12B-A2.5B at one chip's share: RMSNorm,
grouped-query attention with rotary positions (causal within a window on
"sliding" layers; YaRN's table and attention factor on "full" layers), a
softmax router over all E experts with top-k gates renormalised, SwiGLU
experts of which the first H are held, an untied head, and the Switch
load-balance term over all E.

The router scores all E experts; each held expert is computed on every
token and weighted by its gate, which is zero where the token did not
route to it, so the pairs of experts held elsewhere are left out as in
the program.  No sort, no grouping.

Weights are laid out under the leaf paths of the configuration as it is
run, so that the comparison can go leaf by leaf.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from harness import ref_layers as L

BLOCK = "['blocks']"
MOE = f"{BLOCK}['moe']"


def _held(s: dict) -> int:
    return s.get("experts_held") or s["num_experts"]


def param_specs(s: dict) -> dict:
    """{leaf path: (shape, init kind)}."""
    d, n, ff, hd = s["d_model"], s["num_layers"], s["d_ff"], s["head_dim"]
    q, kv = s["num_heads"] * hd, s["num_kv_heads"] * hd
    v, e, h = L.pad_vocab(s["vocab_size"]), s["num_experts"], _held(s)
    return {
        "['embed']": ((v, d), "embed"),
        "['final_norm']": ((d,), "ones"),
        "['lm_head']": ((d, v), "normal"),
        f"{BLOCK}['attn']['wq']": ((n, d, q), "normal"),
        f"{BLOCK}['attn']['wk']": ((n, d, kv), "normal"),
        f"{BLOCK}['attn']['wv']": ((n, d, kv), "normal"),
        f"{BLOCK}['attn']['wo']": ((n, q, d), "normal"),
        f"{BLOCK}['ln0']": ((n, d), "ones"),
        f"{BLOCK}['ln1']": ((n, d), "ones"),
        f"{MOE}['router']": ((n, d, e), "normal"),
        f"{MOE}['wg']": ((n, h, d, ff), "normal"),
        f"{MOE}['wi']": ((n, h, d, ff), "normal"),
        f"{MOE}['wo']": ((n, h, ff, d), "normal"),
    }


#: YaRN's published parameters beside its factor (the configuration file's
#: ``rope_parameters["full_attention"]``).
BETA_FAST, BETA_SLOW, ATTENTION_FACTOR = 32, 1, 1.2772588722239782


def rotary(s: dict, kind: str) -> tuple[np.ndarray, float]:
    """(inverse frequencies, cos/sin scale) of a layer of type ``kind``.
    "full" layers with a YaRN factor: transformers' yarn parameters,
    written out (frequencies interpolated by the factor below the
    correction range of BETA_FAST..BETA_SLOW rotations over the original
    context, extrapolated above it, a linear ramp between)."""
    hd, theta = s["head_dim"], s["rope_theta"]
    base = theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    factor = s.get("yarn_factor", 0.0)
    if kind != "full" or not factor:
        return 1.0 / base, 1.0
    orig = s["yarn_original_max_position"]

    def corr_dim(rot):
        return hd * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr_dim(BETA_FAST)), 0)
    high = min(math.ceil(corr_dim(BETA_SLOW)), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * base)) * ramp + (1.0 / base) * (1.0 - ramp)
    return inv, ATTENTION_FACTOR


def rope(x: jax.Array, inv: np.ndarray, scale: float) -> jax.Array:
    """Rotary embedding over (B, S, H, hd), halves rotated."""
    t = x.shape[1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(mm: L.MatMul, q, k, v, window) -> jax.Array:
    """Causal softmax attention, within ``window`` keys back where given.
    q (B, S, Hq, hd); k, v (B, S, Hkv, hd) -> (B, S, Hq * hd)."""
    b, t, hq, hd = q.shape
    g = hq // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    logits = mm("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    qi, kj = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = qi >= kj
    if window:
        mask = mask & (qi - kj < window)
    probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
    return mm("bhqk,bkhd->bqhd", probs, v).reshape(b, t, hq * hd)


def experts(mm: L.MatMul, a, w: dict, s: dict):
    """The held experts' part of the expert layer, and its Switch term."""
    e, k = s["num_experts"], s["experts_per_token"]
    probs = jax.nn.softmax(mm("bsd,de->bse", a, w["router"]), axis=-1)
    gates, chosen = jax.lax.top_k(probs, k)
    gates = gates / gates.sum(-1, keepdims=True)
    frac = jax.nn.one_hot(jnp.argmax(probs, -1), e).mean((0, 1))
    aux = s["router_aux_weight"] * e * jnp.sum(frac * probs.mean((0, 1)))
    out = jnp.zeros_like(a)
    for x in range(_held(s)):
        gate = jnp.where(chosen == x, gates, 0.0).sum(-1)       # (B, S)
        h = jax.nn.silu(mm("bsd,df->bsf", a, w["wg"][x])) \
            * mm("bsd,df->bsf", a, w["wi"][x])
        out = out + gate[..., None] * mm("bsf,fd->bsd", h, w["wo"][x])
    return out, aux


def loss(p: dict, batch: dict, mm: L.MatMul, s: dict) -> jax.Array:
    """Mean next-token cross-entropy of one worker's (B, S) batch, plus
    the load-balance term of every layer."""
    eps, hd = s["norm_eps"], s["head_dim"]
    types = s["layer_types"]
    x = p["['embed']"][batch["tokens"]]
    b, t, _ = x.shape
    aux = 0.0
    for layer in range(s["num_layers"]):
        kind = types[layer % len(types)]
        w = {k[len(BLOCK):]: v[layer] for k, v in p.items()
             if k.startswith(BLOCK)}
        inv, scale = rotary(s, kind)
        a = L.rms_norm(x, w["['ln0']"], eps)
        q = mm("bsd,dh->bsh", a, w["['attn']['wq']"]).reshape(b, t, -1, hd)
        k = mm("bsd,dh->bsh", a, w["['attn']['wk']"]).reshape(b, t, -1, hd)
        v = mm("bsd,dh->bsh", a, w["['attn']['wv']"]).reshape(b, t, -1, hd)
        o = attention(mm, rope(q, inv, scale), rope(k, inv, scale), v,
                      s["sliding_window"] if kind == "sliding" else None)
        x = x + mm("bsh,hd->bsd", o, w["['attn']['wo']"])
        a = L.rms_norm(x, w["['ln1']"], eps)
        f, aux_l = experts(mm, a, {n: w[f"['moe']['{n}']"] for n in
                                   ("router", "wg", "wi", "wo")}, s)
        x, aux = x + f, aux + aux_l
    x = L.rms_norm(x, p["['final_norm']"], eps)
    logits = mm("bsd,dv->bsv", x, p["['lm_head']"])
    return L.cross_entropy(logits, batch["labels"]) + aux


def matmul_positions(s: dict, traffic: dict) -> dict:
    """{leaf path: input positions per row that the leaf multiplies}.
    Attention, router and head weights multiply every token.  An expert
    leaf multiplies ``seq * k / E`` positions per row, the expected load
    of one expert under uniform routing; the held leaves hold H experts,
    so their product counts H * k / E of a token's worth each.  The
    embedding is a lookup."""
    t = int(traffic["seq"])
    per_expert = t * s["experts_per_token"] / s["num_experts"]
    out = {}
    for k, (shape, kind) in param_specs(s).items():
        if kind == "normal":
            out[k] = per_expert if k.startswith(MOE) and "router" not in k \
                else t
    return out


def attention_flops_per_row(s: dict, traffic: dict) -> float:
    """Forward FLOPs of the score and value products of one row: per
    layer, the query-key pairs inside the causal mask and its window."""
    t = int(traffic["seq"])
    width = s["num_heads"] * s["head_dim"]
    types = s["layer_types"]
    total = 0.0
    for layer in range(s["num_layers"]):
        w = s["sliding_window"] if types[layer % len(types)] == "sliding" \
            else None
        pairs = t * t / 2 if not w or w >= t else w * w / 2 + (t - w) * w
        total += 4.0 * width * pairs
    return total
