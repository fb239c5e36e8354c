"""State-of-the-art Byzantine gradient attacks (paper §6.1 / Appendix 14.3).

Every attack produces the f Byzantine rows given the honest rows.  The
primitive shared by ALIE / FOE / SF is

    B_t = sbar_t + eta * a_t

with sbar_t the honest mean (of gradients for D-GD, momenta for D-SHB).

The *optimized* ALIE/FOE variants (Shejwalkar & Houmansadr, used by the
paper) grid-search eta to maximize || F(attacked stack) - sbar_t ||, i.e.
they are adaptive to the deployed aggregation rule.

Label-flipping is not a vector transformation — it is applied in the data
pipeline (see repro.training.trainer: Byzantine workers compute real
gradients on labels 9 - l).  `lf` here is a passthrough marker.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

Array = jax.Array

# eta grid for the optimized attacks (log-ish spacing around the published
# sweet spots; ALIE's published z* for n=17,f=4 is ~0.3-1.5, FOE's ~0.1-10).
_ETA_GRID = (0.05, 0.1, 0.2, 0.5, 0.8, 1.0, 1.5, 2.0, 3.0, 5.0, 10.0, 20.0)


def _finite_row_mask(h: Array) -> Array:
    """(n,) bool: rows of the (n, ...) stack whose entries are all finite."""
    return jnp.isfinite(h.reshape(h.shape[0], -1)).all(axis=1)


def _finite_moments(h: Array) -> tuple[Array, Array]:
    """Coordinate-wise (mean, std) of an fp32 stack, excluding non-finite
    rows.

    A faulty worker emitting nan/inf (the `nan`/`inf` families, fp
    overflow, bad data) must not poison the moment-based attacks' own
    statistics — an ALIE row of nan is trivially filtered by any robust
    rule, which would silently neuter the attack.  When every row is
    finite, the plain mean/std path is selected, bitwise unchanged.
    """
    finite = _finite_row_mask(h)
    sel = finite.reshape((-1,) + (1,) * (h.ndim - 1))
    cnt = jnp.maximum(finite.astype(jnp.float32).sum(), 1.0)
    hz = jnp.where(sel, h, 0.0)
    mean_m = hz.sum(axis=0) / cnt
    var_m = jnp.where(sel, (h - mean_m) ** 2, 0.0).sum(axis=0) / cnt
    all_finite = finite.all()
    mean = jnp.where(all_finite, h.mean(axis=0), mean_m)
    std = jnp.where(all_finite, h.std(axis=0), jnp.sqrt(var_m))
    return mean, std


def _mean_std(honest: Array) -> tuple[Array, Array]:
    return _finite_moments(honest.astype(jnp.float32))


def alie(honest: Array, f: int, eta: float = 1.0, **_) -> Array:
    """A Little Is Enough: sbar + eta * coordinate-wise std."""
    mean, std = _mean_std(honest)
    byz = mean + eta * std
    return jnp.broadcast_to(byz, (f,) + byz.shape)


def foe(honest: Array, f: int, eta: float = 2.0, **_) -> Array:
    """Fall of Empires: (1 - eta) * sbar  (a_t = -sbar)."""
    mean, _ = _mean_std(honest)
    byz = (1.0 - eta) * mean
    return jnp.broadcast_to(byz, (f,) + byz.shape)


def sign_flip(honest: Array, f: int, **_) -> Array:
    """Sign flipping: B_t = -sbar (FOE with eta = 2)."""
    return foe(honest, f, eta=2.0)


def mimic(honest: Array, f: int, *, target: Optional[Array] = None, **_) -> Array:
    """Mimic: all Byzantine workers copy one honest worker.

    Paper heuristic [26]: mimic the honest worker most aligned with the top
    principal direction of the honest stack — approximated here by one power
    iteration from the honest mean-centered stack (cheap and jit-safe).
    `target` overrides with an explicit worker index.
    """
    h = honest.astype(jnp.float32)
    if target is None:
        centered = h - h.mean(axis=0, keepdims=True)
        # One power-iteration step: v ~ top eigvec of centered^T centered.
        # Seed with the per-coordinate energy diag(C^T C): the all-ones /
        # row-sum seed lies in the centered stack's null space, leaving the
        # iteration to amplify rounding noise.
        v = (centered ** 2).sum(axis=0)
        v = centered.T @ (centered @ v)
        norm = jnp.linalg.norm(v) + 1e-12
        scores = centered @ (v / norm)
        target = jnp.argmax(jnp.abs(scores))
    byz = h[target]
    return jnp.broadcast_to(byz, (f,) + byz.shape)


def _optimized(base: Callable, honest: Array, f: int, agg_closure: Callable,
               **kw) -> Array:
    """Grid-search eta maximizing ||F(attacked) - honest mean||.

    agg_closure: (full stack (n, d)) -> (d,) — the deployed aggregator,
    including pre-aggregation; the attacker is assumed omniscient (worst
    case), per the paper's optimized ALIE/FOE protocol.
    """
    mean = honest.astype(jnp.float32).mean(axis=0)

    def damage(eta):
        byz = base(honest, f, eta=eta, **kw)
        out = agg_closure(jnp.concatenate([honest.astype(jnp.float32), byz]))
        return jnp.sum((out - mean) ** 2)

    etas = jnp.asarray(_ETA_GRID, dtype=jnp.float32)
    damages = jax.lax.map(damage, etas)
    best = etas[jnp.argmax(damages)]
    return base(honest, f, eta=best, **kw)


def alie_opt(honest: Array, f: int, *, agg_closure: Callable, **kw) -> Array:
    return _optimized(alie, honest, f, agg_closure, **kw)


def foe_opt(honest: Array, f: int, *, agg_closure: Callable, **kw) -> Array:
    return _optimized(foe, honest, f, agg_closure, **kw)


def nan_rows(honest: Array, f: int, **_) -> Array:
    """Non-finite fault family: f rows of NaN.

    Models a crashed/faulty worker (bad data, fp exceptions) rather than an
    optimizing adversary — the oracle the in-round quarantine guard
    (:mod:`repro.robustness.guard`) is tested against.
    """
    byz = jnp.full(honest.shape[1:], jnp.nan, jnp.float32)
    return jnp.broadcast_to(byz, (f,) + byz.shape)


def inf_rows(honest: Array, f: int, **_) -> Array:
    """f rows of +inf (fp overflow fault)."""
    byz = jnp.full(honest.shape[1:], jnp.inf, jnp.float32)
    return jnp.broadcast_to(byz, (f,) + byz.shape)


ATTACKS: dict[str, Callable] = {
    "alie": alie,
    "foe": foe,
    "sf": sign_flip,
    "mimic": mimic,
    "alie_opt": alie_opt,
    "foe_opt": foe_opt,
    "nan": nan_rows,
    "inf": inf_rows,
}


def _require_agg_closure(name: str, agg_closure) -> None:
    """Optimized attacks grid-search eta against the DEPLOYED aggregator;
    without the closure there is nothing to optimize against."""
    if name.endswith("_opt") and agg_closure is None:
        raise ValueError(
            f"optimized attack {name!r} requires agg_closure= (the deployed "
            "aggregation rule as a stack -> aggregate callable); pass it or "
            f"use the non-adaptive {name.removesuffix('_opt')!r}")


def apply_attack(name: str, honest: Array, f: int, **kw) -> Array:
    """Attacked full stack (n, d): honest rows followed by f Byzantine rows.

    name == "none" or "lf" returns honest rows untouched on the vector side
    (LF acts through the data pipeline).
    """
    if f == 0 or name in ("none", "lf"):
        # For "lf" the Byzantine rows are honest *computations* on flipped
        # labels and already live in `honest`'s companion rows upstream.
        return honest
    if name not in ATTACKS:
        raise ValueError(f"unknown attack {name!r}; known: {sorted(ATTACKS)}")
    _require_agg_closure(name, kw.get("agg_closure"))
    byz = ATTACKS[name](honest, f, **kw)
    return jnp.concatenate([honest.astype(jnp.float32), byz], axis=0)


# ---------------------------------------------------------------------------
# Pytree-stack attacks (distributed trainer integration).
#
# Leaves carry a leading worker axis; honest rows are [: n-f], Byzantine
# rows [n-f :] get overwritten.  Coordinate-wise primitives apply leaf-wise;
# Mimic's global target selection runs in gram space (n x n replicated).
# ---------------------------------------------------------------------------

def _tree_honest(tree, n_honest):
    return jax.tree_util.tree_map(lambda l: l[:n_honest], tree)


def apply_attack_tree(name: str, tree, f: int, *, eta: float | None = None,
                      agg_closure: Callable | None = None,
                      eta_grid: tuple = _ETA_GRID):
    """Attacked worker-stacked pytree (worker axis leading on every leaf).

    ``agg_closure`` (tree -> aggregated tree) enables the optimized
    ALIE/FOE eta line search, evaluated on the full pytree.
    """
    if f == 0 or name in ("none", "lf"):
        return tree
    leaves = jax.tree_util.tree_leaves(tree)
    n = leaves[0].shape[0]
    nh = n - f

    def leafwise(make_byz):
        def go(leaf):
            h = leaf[:nh].astype(jnp.float32)
            byz = make_byz(h)
            out = jnp.concatenate([h, jnp.broadcast_to(byz, (f,) + byz.shape)])
            return out.astype(leaf.dtype)
        return jax.tree_util.tree_map(go, tree)

    if name in ("nan", "inf"):
        fill = jnp.nan if name == "nan" else jnp.inf
        return leafwise(lambda h: jnp.full(h.shape[1:], fill, jnp.float32))

    if name in ("alie", "foe", "sf", "alie_opt", "foe_opt"):
        base = name.split("_")[0]
        if name.endswith("_opt"):
            _require_agg_closure(name, agg_closure)
            best_eta = _tree_eta_search(base, tree, nh, f, agg_closure, eta_grid)
        else:
            best_eta = eta if eta is not None else (1.0 if base == "alie" else 2.0)
        # _finite_moments (not plain mean/std): an honest row of nan/inf
        # must not leak into the Byzantine vector — see its docstring.
        if base == "alie":
            mk = lambda h: (lambda ms: ms[0] + best_eta * ms[1])(
                _finite_moments(h))
        else:  # foe / sf
            e = 2.0 if name == "sf" else best_eta
            mk = lambda h: (1.0 - e) * _finite_moments(h)[0]
        return leafwise(mk)

    if name == "mimic":
        from repro.core import robust as robust_lib
        honest = _tree_honest(tree, nh)
        g = robust_lib.tree_gram(honest)
        # Gram of the centered stack: C = (I - 11^T/n) G (I - 11^T/n)
        c = g - g.mean(0, keepdims=True) - g.mean(1, keepdims=True) + g.mean()
        # One power iteration in coefficient space, seeded with diag(c)
        # (centered row energies) — the ones vector is in c's null space.
        v = c @ (c @ jnp.diagonal(c))
        scores = jnp.abs(v)
        target = jnp.argmax(scores)
        return leafwise(lambda h: h[target])

    raise ValueError(f"unknown attack {name!r}")


# ---------------------------------------------------------------------------
# Scan-phase attacks (round engine).
#
# A scanned multi-round run resolves its attack SCHEDULE host-side into a
# per-round branch index, but keeps the Byzantine count f STATIC (it is
# constant within a run) — so every branch can replay the static
# `apply_attack_tree` math exactly.  This is what makes a scanned fed run
# bit-for-bit equal to the per-round loop it replaces, even when the
# schedule switches family mid-chunk.  Contrast `apply_attack_dyn` below:
# traced f forces masked statistics, which are only float-close to the
# static slices.
# ---------------------------------------------------------------------------

def apply_attack_scan(families: tuple[str, ...], attack_id: Array, tree,
                      f: int, *, eta: Array,
                      agg_closure: Callable | None = None):
    """Attacked worker-stacked pytree with a TRACED family, STATIC f.

    ``families`` is the static branch tuple (the run's schedule families,
    jit-cache key material); ``attack_id`` selects the branch per round.
    Branch b computes ``apply_attack_tree(families[b], tree, f, ...)``
    verbatim: ``eta`` is passed through only for the families that consume
    a traced eta (alie/foe — matching the fed server's ``use_eta``
    convention), and ``agg_closure`` only reaches the optimized variants.
    Outside a vmap, `lax.switch` executes ONE branch per round.
    """
    if f == 0 or not families:
        return tree
    for name in families:
        if name not in ("none", "lf") and name not in ATTACKS:
            raise ValueError(f"unknown attack {name!r}; known: "
                             f"{('none', 'lf') + tuple(sorted(ATTACKS))}")
        _require_agg_closure(name, agg_closure)

    def branch(name: str):
        def run():
            use_eta = name in ("alie", "foe")
            return apply_attack_tree(name, tree, f,
                                     eta=eta if use_eta else None,
                                     agg_closure=agg_closure)
        return run

    if len(families) == 1:
        return branch(families[0])()
    return jax.lax.switch(attack_id, [branch(n) for n in families])


# ---------------------------------------------------------------------------
# Lane-dynamic attacks (fleet engine).
#
# The attack FAMILY becomes a traced int32 selecting a `lax.switch` branch,
# and the Byzantine count and eta become traced scalars, so one compiled
# round serves lanes running different adversaries.  Honest statistics are
# computed with row masks (row < n - f) instead of static slices.  The
# optimized (_opt) variants are excluded: their eta line search re-runs the
# deployed aggregator per grid point, which under vmap+switch would execute
# for EVERY lane every round — schedule them through the static per-family
# path instead.
# ---------------------------------------------------------------------------

#: switch branch order of :func:`apply_attack_dyn`; "lf" and "none" share
#: the passthrough branch (LF acts through the data pipeline).  APPEND-only:
#: the indices are jit-cache and fleet-operand material.
DYN_ATTACK_FAMILIES = ("none", "alie", "foe", "sf", "mimic", "nan", "inf")


def dyn_attack_id(name: str) -> int:
    """Map an attack name to its `apply_attack_dyn` branch index."""
    if name == "lf":
        return 0
    if name in ("alie_opt", "foe_opt"):
        raise ValueError(
            f"{name!r} is not lane-dynamic (its eta search re-runs the "
            "aggregator per grid point); run it through the static path")
    if name not in DYN_ATTACK_FAMILIES:
        raise ValueError(f"unknown attack {name!r}; lane-dynamic families: "
                         f"{DYN_ATTACK_FAMILIES} (+ 'lf')")
    return DYN_ATTACK_FAMILIES.index(name)


def _masked_moments(tree, w, nh: Array):
    """Per-leaf (mean, std) over the first n-f rows, traced nh = n - f.

    Rows are excluded via `jnp.where` row selection rather than
    multiplication (0.0 * nan = nan), and honest rows containing non-finite
    entries are dropped from the statistics with the count adjusted — a
    faulty worker must not propagate nan/inf through the moment-based
    families (see `_finite_moments`).  For all-finite stacks the selected
    count equals nh exactly, so the masked arithmetic is unchanged.
    """
    stats = []
    for leaf in jax.tree_util.tree_leaves(tree):
        h = leaf.astype(jnp.float32)
        w_eff = w * _finite_row_mask(h).astype(jnp.float32)
        sel = (w_eff > 0).reshape((-1,) + (1,) * (h.ndim - 1))
        cnt = jnp.maximum(w_eff.sum(), 1.0)
        mean = jnp.where(sel, h, 0.0).sum(0) / cnt
        var = jnp.where(sel, (h - mean) ** 2, 0.0).sum(0) / cnt
        stats.append((mean, jnp.sqrt(var)))
    return stats


def apply_attack_dyn(attack_id: Array, tree, f: Array, *, eta: Array):
    """Attacked worker-stacked pytree with TRACED (family, f, eta).

    ``attack_id`` indexes :data:`DYN_ATTACK_FAMILIES`; rows >= n - f of
    every leaf are overwritten by the selected family's Byzantine vector.
    f == 0 (or the passthrough branch) leaves the stack untouched.  All
    branch outputs share the stack's structure/shapes, as `lax.switch`
    requires; under vmap every branch executes and the result is selected
    per lane — the branches are O(n d) / one O(n^2 d) gram (mimic), cheap
    next to the client pass.
    """
    leaves = jax.tree_util.tree_leaves(tree)
    n = leaves[0].shape[0]
    nh = (n - f).astype(jnp.int32)
    row = jnp.arange(n)
    w = (row < nh).astype(jnp.float32)
    stats = _masked_moments(tree, w, nh)
    treedef = jax.tree_util.tree_structure(tree)

    def from_byz(byz_values):
        """Broadcast per-leaf Byzantine vectors over the full stack shape."""
        out = []
        for leaf, byz in zip(leaves, byz_values):
            out.append(jnp.broadcast_to(byz, leaf.shape).astype(jnp.float32))
        return out

    def br_passthrough():
        return [leaf.astype(jnp.float32) for leaf in leaves]

    def br_alie():
        return from_byz([m + eta * s for m, s in stats])

    def br_foe():
        return from_byz([(1.0 - eta) * m for m, _ in stats])

    def br_sf():
        return from_byz([-m for m, _ in stats])

    def br_mimic():
        # Target = honest row most aligned with the honest stack's top
        # principal direction, via one power iteration in coefficient space
        # (same scheme as the static path, with byz rows masked out).
        from repro.core import robust as robust_lib
        centered = []
        for leaf, (mean, _) in zip(leaves, stats):
            h = leaf.astype(jnp.float32)
            # where-select (not multiply: 0 * nan = nan) and drop non-finite
            # honest rows, so a faulty row cannot poison the target scores.
            keep = (w * _finite_row_mask(h).astype(jnp.float32)) > 0
            sel = keep.reshape((-1,) + (1,) * (h.ndim - 1))
            centered.append(jnp.where(sel, h - mean, 0.0))
        c = robust_lib.tree_gram(jax.tree_util.tree_unflatten(treedef, centered))
        # Same diag(c) power-iteration seed as the static path (byz rows of
        # the masked centered gram are zero, so their scores stay zero).
        v = c @ (c @ jnp.diagonal(c))
        scores = jnp.abs(v) * w
        target = jnp.argmax(scores)
        return from_byz([leaf.astype(jnp.float32)[target] for leaf in leaves])

    def br_nan():
        return from_byz([jnp.full(leaf.shape[1:], jnp.nan, jnp.float32)
                         for leaf in leaves])

    def br_inf():
        return from_byz([jnp.full(leaf.shape[1:], jnp.inf, jnp.float32)
                         for leaf in leaves])

    byz = jax.lax.switch(attack_id,
                         (br_passthrough, br_alie, br_foe, br_sf, br_mimic,
                          br_nan, br_inf))
    byz_rows = row >= nh

    out_leaves = []
    for leaf, b in zip(leaves, byz):
        mask = byz_rows.reshape((-1,) + (1,) * (leaf.ndim - 1))
        out_leaves.append(
            jnp.where(mask, b, leaf.astype(jnp.float32)).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


def _tree_eta_search(base: str, tree, nh: int, f: int, agg_closure, eta_grid):
    """Pick eta maximizing || F(attacked) - honest mean ||^2 over the tree."""
    honest = _tree_honest(tree, nh)

    def damage(eta):
        attacked = apply_attack_tree(base, tree, f, eta=eta)
        agg = agg_closure(attacked)
        tot = 0.0
        for a, h in zip(jax.tree_util.tree_leaves(agg),
                        jax.tree_util.tree_leaves(honest)):
            mean = h.astype(jnp.float32).mean(0)
            tot = tot + jnp.sum((a.astype(jnp.float32) - mean) ** 2)
        return tot

    etas = jnp.asarray(eta_grid, jnp.float32)
    damages = jax.lax.map(damage, etas)
    return etas[jnp.argmax(damages)]
