"""Mixture-of-Experts: top-k routing over every expert, the held experts'
part of the result, no token dropped.

The layer is told which experts it holds: experts 0 .. H-1 of the E that
the router scores (``cfg.experts_held``; 0 = all E), one device's share
under expert parallelism.  Every token is routed over all E (float32
logits, softmax, top-k, gates renormalised over the k chosen), and every
(token, expert) pair whose expert is held is computed.  Pairs routed to
an expert held elsewhere are left out: the layer returns the held
experts' part of the result, which is what goes on to the next layer.

Dispatch is sort-and-segment.  The t*k pairs of a group of t tokens are
sorted by expert, held experts first, and the three SwiGLU products run
as grouped matmuls (``jax.lax.ragged_dot``) whose groups are sized by the
pairs routed to each held expert.  The sorted buffer keeps t*min(k, H)
rows, the most pairs t tokens can route to H experts, so there is no
capacity and nothing drops.  Rows past the routed pairs belong to no
group and their gathers and scatters fall out of range.

Expert weights hold only the H experts, (H, d, ff); the router keeps all
E outputs.  The per-worker train step vmaps the loss: the grouped
products batch as a loop over the mapped axis (``ragged_dot`` batches
only with every operand mapped, and not under ``grad``), and their
backward is written out (:func:`_experts`), so that each worker's expert
gradient is its own.

Tags (``repro.obs.stages.moe_part``): ``moe_part="route"`` on the router,
top-k, sort, gathers, scatters and the combine; ``moe_part="experts"`` on
the grouped products, forward and backward.

FSDP experts (giant MoE; DESIGN.md §Arch-applicability): when the mesh
axes context sets ``expert_fsdp``, expert weights additionally shard over
the data axes (ZeRO-3 style) — at the cost of per-worker expert
gradients never existing (selective robustness; see
repro.training.trainer).

Includes the standard load-balance auxiliary loss (Switch eq. 4) over
all E experts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamDesc
from repro.obs.stages import moe_part

Array = jax.Array
_RD = jax.lax.RaggedDotDimensionNumbers


def moe_params(cfg: ModelConfig, layers: int) -> dict:
    d, ff, e, h = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.held_experts
    L = (layers,) if layers else ()
    lax = ("layers",) if layers else ()
    p = {
        "router": ParamDesc(L + (d, e), cfg.dtype, lax + ("embed", "expert")),
        "wi": ParamDesc(L + (h, d, ff), cfg.dtype,
                        lax + ("expert", "expert_embed", "ff_inner")),
        "wg": ParamDesc(L + (h, d, ff), cfg.dtype,
                        lax + ("expert", "expert_embed", "ff_inner")),
        "wo": ParamDesc(L + (h, ff, d), cfg.dtype,
                        lax + ("expert", "ff_inner", "expert_embed")),
    }
    if cfg.moe_dense_ff:
        from repro.models import mlp
        p["dense"] = mlp.swiglu_params(cfg, layers, d_ff=cfg.moe_dense_ff)
    return p


# ---------------------------------------------------------------------------
# Grouped products: rows (m, .) sorted by group, sizes (g,) rows per group.
# ---------------------------------------------------------------------------

def _looped(fn):
    """``fn`` whose vmap is a loop over the mapped axis."""
    f = jax.custom_batching.custom_vmap(fn)

    @f.def_vmap
    def rule(axis_size, in_batched, *args):
        outs = [f(*(a[i] if b else a for a, b in zip(args, in_batched)))
                for i in range(axis_size)]
        return jnp.stack(outs), True
    return f


@_looped
def _gmm(x, w, sizes):
    """(m, a) rows times their group's (a, b) weight -> (m, b)."""
    return jax.lax.ragged_dot(x, w, sizes)


@_looped
def _gmm_t(y, w, sizes):
    """(m, b) rows times their group's (a, b) weight transposed -> (m, a)."""
    return jax.lax.ragged_dot_general(
        y, w, sizes, _RD((([1], [2]), ([], [])), [0], [0]))


@_looped
def _tgmm(x, y, sizes):
    """Per group, its rows of x (m, a) against theirs of y (m, b) ->
    (g, a, b): the weight gradient of a grouped product."""
    return jax.lax.ragged_dot_general(
        x, y, sizes, _RD((([0], [0]), ([], [])), [0], []))


@jax.custom_vjp
def _experts(rows, wg, wi, wo, sizes):
    """SwiGLU of each row through its group's expert: (m, d) -> (m, d)."""
    return _experts_fwd(rows, wg, wi, wo, sizes)[0]


def _experts_fwd(rows, wg, wi, wo, sizes):
    with moe_part("experts"):
        a = _gmm(rows, wg, sizes)
        b = _gmm(rows, wi, sizes)
        y = _gmm(jax.nn.silu(a) * b, wo, sizes)
    return y, (rows, wg, wi, wo, sizes, a, b)


def _experts_bwd(res, dy):
    rows, wg, wi, wo, sizes, a, b = res
    with moe_part("experts"):
        af, bf = a.astype(jnp.float32), b.astype(jnp.float32)
        sig = jax.nn.sigmoid(af)
        h = (af * sig * bf).astype(a.dtype)
        dh = _gmm_t(dy, wo, sizes).astype(jnp.float32)
        da = (dh * bf * sig * (1 + af * (1 - sig))).astype(a.dtype)
        db = (dh * af * sig).astype(b.dtype)
        drows = _gmm_t(da, wg, sizes) + _gmm_t(db, wi, sizes)
        dwg = _tgmm(rows, da, sizes).astype(wg.dtype)
        dwi = _tgmm(rows, db, sizes).astype(wi.dtype)
        dwo = _tgmm(h, dy, sizes).astype(wo.dtype)
    return drows.astype(rows.dtype), dwg, dwi, dwo, None


_experts.defvjp(_experts_fwd, _experts_bwd)


# ---------------------------------------------------------------------------
# The layer.
# ---------------------------------------------------------------------------

def zero_stats() -> dict:
    """Routing counts of no layer: the identity of :func:`merge_stats`."""
    return {"routed_pairs": jnp.zeros((), jnp.int32),
            "expert_load_max": jnp.zeros((), jnp.int32),
            "expert_load_min": jnp.full((), jnp.iinfo(jnp.int32).max,
                                        jnp.int32)}


def merge_stats(a: dict, b: dict) -> dict:
    return {"routed_pairs": a["routed_pairs"] + b["routed_pairs"],
            "expert_load_max": jnp.maximum(a["expert_load_max"],
                                           b["expert_load_max"]),
            "expert_load_min": jnp.minimum(a["expert_load_min"],
                                           b["expert_load_min"])}


def moe_block(p: dict, x: Array, cfg: ModelConfig
              ) -> tuple[Array, Array, dict]:
    """x: (B, S, d) -> (the held experts' part (B, S, d), aux loss, routing
    counts).  The counts: (token, held expert) pairs computed, and the
    largest and smallest number of them that one held expert took."""
    b, s, d = x.shape
    e, k, held = cfg.num_experts, cfg.experts_per_token, cfg.held_experts
    t = b * s
    xt = x.reshape(t, d)

    with moe_part("route"):
        logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)               # (t, e)
        gates, chosen = jax.lax.top_k(probs, k)               # (t, k)
        gates = gates / gates.sum(axis=-1, keepdims=True)

        # Load-balance aux (Switch): e * sum_e(fraction_e * router_prob_e).
        top1 = jnp.argmax(probs, axis=-1)
        frac = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), axis=0)
        aux = cfg.router_aux_weight * e * jnp.sum(frac * probs.mean(axis=0))

        # Sort the pairs by expert, held ones first; pairs of experts held
        # elsewhere take the key ``held`` and sort past the kept rows.
        flat = chosen.reshape(-1)
        key = jnp.where(flat < held, flat, held)
        order = jnp.argsort(key, stable=True)[: t * min(k, held)]
        sizes = jnp.zeros((held,), jnp.int32).at[key].add(1, mode="drop")
        routed = jnp.arange(order.shape[0]) < sizes.sum()
        tok = jnp.where(routed, order // k, t)              # t: out of range
        gate = jnp.where(routed, gates.reshape(-1)[order], 0.0)
        rows = xt.at[tok].get(mode="fill", fill_value=0)

    y = _experts(rows, p["wg"], p["wi"], p["wo"], sizes)

    with moe_part("route"):
        out = jnp.zeros((t, d), jnp.float32).at[tok].add(
            y.astype(jnp.float32) * gate[:, None], mode="drop")
        out = out.astype(x.dtype).reshape(b, s, d)
        stats = {"routed_pairs": sizes.sum(),
                 "expert_load_max": sizes.max(),
                 "expert_load_min": sizes.min()}

    if "dense" in p:                                         # arctic residual
        from repro.models import mlp
        out = out + mlp.swiglu(p["dense"], x)
    return out, aux, stats
