"""Gram-space machinery for distributed robust aggregation.

Krum, Multi-Krum, GM (Weiszfeld) and MDA depend on the worker stack
``x : (n, d)`` only through its Gram matrix ``G = x @ x.T`` (n x n).  On a
pod, G is accumulated leaf-by-leaf / block-by-block with a worker-axis
all-gather and a feature contraction, and the final output is a linear
combination ``coeff @ x``.  This module implements the *small replicated*
side of that pipeline: everything that maps G -> coefficients.

All functions are jit-safe and operate on fp32 n x n matrices.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def gram(x: Array) -> Array:
    """Plain Gram matrix of a (n, d) stack in fp32."""
    x = x.astype(jnp.float32)
    return x @ x.T


def pdist_sq_from_gram(g: Array) -> Array:
    """Pairwise squared distances ||x_i - x_j||^2 from the Gram matrix."""
    diag = jnp.diagonal(g)
    d2 = diag[:, None] - 2.0 * g + diag[None, :]
    # Numerical floor: distances are nonnegative; bf16/fp32 rounding can
    # produce tiny negatives that break sqrt/sort stability downstream.
    return jnp.maximum(d2, 0.0)


def mixed_gram(g: Array, m: Array) -> Array:
    """Gram matrix of the mixed stack Y = M @ X, i.e. M G M^T."""
    return m @ g @ m.T


# ---------------------------------------------------------------------------
# Neighbor selection / scoring (all O(n^2) replicated math).
#
# f is a python int or a TRACED int32 scalar (a fleet lane's budget, so one
# compiled round serves every f).  Shapes stay static either way: an int
# selects through static top_k slices, a tracer through rank masks.
# ---------------------------------------------------------------------------

def _row_ranks(d2: Array) -> Array:
    """rank[i, j] = position of j in ascending order of row i (0 = nearest)."""
    order = jnp.argsort(d2, axis=1)
    return jnp.argsort(order, axis=1)


def nnm_matrix(d2: Array, f) -> Array:
    """NNM mixing matrix from squared distances.

    Row i averages the n-f nearest neighbors of x_i (itself included, since
    d(i,i)=0 is always minimal).  Returns an (n, n) row-stochastic matrix M
    such that Y = M @ X is the NNM output (paper Alg. 2).
    """
    n = d2.shape[0]
    if not isinstance(f, int):
        mask = (_row_ranks(d2) < (n - f)).astype(jnp.float32)
        return mask / (n - f).astype(jnp.float32)
    k = n - f
    # Indices of the k smallest distances per row.
    _, idx = jax.lax.top_k(-d2, k)
    mask = jax.nn.one_hot(idx, n, dtype=jnp.float32).sum(axis=1)
    return mask / float(k)


def _krum_scores(d2: Array, f) -> Array:
    """Sum of the n-f smallest distances per candidate row."""
    n = d2.shape[0]
    if not isinstance(f, int):
        srt = jnp.sort(d2, axis=1)
        keep = (jnp.arange(n)[None, :] < (n - f)).astype(jnp.float32)
        return (srt * keep).sum(axis=1)
    neigh, _ = jax.lax.top_k(-d2, n - f)   # negated distances, k smallest
    return -neigh.sum(axis=1)


def krum_coeff(d2: Array, f) -> Array:
    """One-hot selection vector for (our adaptation of) Krum.

    Scores each candidate j by the sum of squared distances to its n-f
    nearest neighbors (paper §8.1.2, discarding f furthest) and selects the
    argmin.  Output c satisfies Krum(x) = c @ x.
    """
    n = d2.shape[0]
    scores = _krum_scores(d2, f)
    return jax.nn.one_hot(jnp.argmin(scores), n, dtype=jnp.float32)


def multikrum_coeff(d2: Array, f) -> Array:
    """Multi-Krum: average of the n-f best Krum-scoring candidates."""
    n = d2.shape[0]
    scores = _krum_scores(d2, f)
    if not isinstance(f, int):
        rank = jnp.argsort(jnp.argsort(scores))
        sel = (rank < (n - f)).astype(jnp.float32)
        return sel / (n - f).astype(jnp.float32)
    k = n - f
    _, best = jax.lax.top_k(-scores, k)
    c = jax.nn.one_hot(best, n, dtype=jnp.float32).sum(axis=0)
    return c / float(k)


def gm_coeff(g: Array, f: int, iters: int = 8, eps: float = 1e-8) -> Array:
    """Weiszfeld coefficients for the geometric median, in gram space.

    Maintains y = w @ x implicitly via its coefficient vector w.  The
    distances ||y - x_i|| needed by each Weiszfeld step are computed from G:
        ||y - x_i||^2 = w G w^T - 2 (G w)_i + G_ii.
    Uses the smoothed update of Pillutla et al. (the approximation the paper
    itself uses, ref [38]).
    """
    del f  # GM does not need f; kept for interface uniformity.
    n = g.shape[0]
    diag = jnp.diagonal(g)

    def step(w, _):
        gw = g @ w
        quad = w @ gw
        d2 = jnp.maximum(diag - 2.0 * gw + quad, 0.0)
        inv = 1.0 / jnp.sqrt(d2 + eps)
        w_new = inv / inv.sum()
        return w_new, None

    w0 = jnp.full((n,), 1.0 / n, dtype=jnp.float32)
    w, _ = jax.lax.scan(step, w0, None, length=iters)
    return w


def project_simplex(v: Array) -> Array:
    """Euclidean projection of v onto the probability simplex.

    Sort-based algorithm of Duchi et al. (2008) with static shapes: the
    support size rho is found as the count of active conditions (monotone
    in the sorted order), so the whole projection is jit- and vmap-safe.
    """
    n = v.shape[0]
    u = jnp.sort(v)[::-1]
    css = jnp.cumsum(u)
    idx = jnp.arange(1, n + 1, dtype=jnp.float32)
    cond = u + (1.0 - css) / idx > 0.0
    # cond is True on a prefix (u sorted descending), and always at idx=1.
    rho = jnp.maximum(cond.astype(jnp.int32).sum() - 1, 0)
    theta = (1.0 - jnp.take(css, rho)) / (rho + 1).astype(jnp.float32)
    return jnp.maximum(v + theta, 0.0)


def autogm_coeff(g: Array, f, *, lamb: float = 1.0, outer_iters: int = 4,
                 gm_iters: int = 8, gm_eps: float = 1e-8) -> Array:
    """Adaptively-weighted geometric median (AutoGM), in gram space.

    Alternating minimization of

        sum_i w_i ||z - x_i||  +  lamb' ||w||^2     over  w in simplex, z

    where the z-step is a weighted Weiszfeld solve (distances from G, as in
    :func:`gm_coeff`) and the w-step is the closed-form simplex projection
    of -d / (2 lamb').  ``lamb`` is expressed in units of the mean distance
    to the uniform-weight GM, making the weight solve invariant to gradient
    scale; lamb -> inf recovers plain GM, lamb -> 0 concentrates all weight
    on the nearest point.  Everything is fixed-iteration ``lax.scan`` math
    on the replicated (n, n) Gram matrix, so the rule runs unchanged inside
    scanned rounds, under vmap (fleet lanes), and with a traced f — which,
    like GM, it never reads.
    """
    del f  # AutoGM adapts weights from distances; kept for uniformity.
    n = g.shape[0]
    diag = jnp.diagonal(g)

    def dists(c):
        gc = g @ c
        quad = c @ gc
        return jnp.sqrt(jnp.maximum(diag - 2.0 * gc + quad, 0.0) + gm_eps)

    def weiszfeld(w, c0):
        def step(c, _):
            inv = w / dists(c)
            return inv / jnp.maximum(inv.sum(), gm_eps), None
        c, _ = jax.lax.scan(step, c0, None, length=gm_iters)
        return c

    uniform = jnp.full((n,), 1.0 / n, dtype=jnp.float32)
    c = weiszfeld(uniform, uniform)
    lamb_eff = jnp.maximum(jnp.float32(lamb) * dists(c).mean(),
                           jnp.float32(gm_eps))

    def outer(carry, _):
        _, c = carry
        w = project_simplex(-dists(c) / (2.0 * lamb_eff))
        return (w, weiszfeld(w, c)), None

    (_, c), _ = jax.lax.scan(outer, (uniform, c), None, length=outer_iters)
    return c


# ---------------------------------------------------------------------------
# MDA: minimum-diameter averaging.
# ---------------------------------------------------------------------------

_MDA_EXACT_LIMIT = 60_000


def _subsets(n: int, f: int) -> np.ndarray:
    """All (n-f)-subsets of [n] as an int32 array (static, host-side)."""
    combos = list(itertools.combinations(range(n), n - f))
    return np.asarray(combos, dtype=np.int32)


def mda_coeff(d2: Array, f: int) -> Array:
    """Coefficients of minimum-diameter averaging.

    Exact subset enumeration for C(n, f) <= 60k (covers the paper's n=17,
    f<=8); greedy diameter pruning beyond (iteratively drop the point with
    the largest max-distance) — documented in DESIGN.md.
    """
    if not isinstance(f, int):
        raise ValueError("mda has no traced-f form (subset enumeration is "
                         "shape-level); use a python int f or another rule")
    n = d2.shape[0]
    import math
    if f == 0:
        return jnp.full((n,), 1.0 / n, dtype=jnp.float32)
    if math.comb(n, f) <= _MDA_EXACT_LIMIT:
        subs = jnp.asarray(_subsets(n, f))          # (S, n-f)
        sub_d = d2[subs[:, :, None], subs[:, None, :]]  # (S, n-f, n-f)
        diam = sub_d.max(axis=(1, 2))
        best = subs[jnp.argmin(diam)]
        c = jax.nn.one_hot(best, n, dtype=jnp.float32).sum(axis=0)
        return c / float(n - f)
    # Greedy: drop the worst point f times.
    alive = jnp.ones((n,), dtype=jnp.float32)

    def drop(alive, _):
        masked = jnp.where(alive[None, :] * alive[:, None] > 0, d2, -jnp.inf)
        worst = jnp.argmax(masked.max(axis=1))
        return alive.at[worst].set(0.0), None

    alive, _ = jax.lax.scan(drop, alive, None, length=f)
    return alive / alive.sum()


def coeff_for_rule(rule: str, g: Array, f, *, gm_iters: int = 8,
                   gm_eps: float = 1e-8, autogm_lamb: float = 1.0,
                   autogm_iters: int = 4) -> Array:
    """Dispatch: Gram matrix -> linear-combination coefficients.

    GM and AutoGM never read f; MDA needs a python int."""
    n = g.shape[0]
    if rule == "average":
        return jnp.full((n,), 1.0 / n, dtype=jnp.float32)
    if rule == "gm":
        return gm_coeff(g, f, iters=gm_iters, eps=gm_eps)
    if rule == "autogm":
        return autogm_coeff(g, f, lamb=autogm_lamb, outer_iters=autogm_iters,
                            gm_iters=gm_iters, gm_eps=gm_eps)
    d2 = pdist_sq_from_gram(g)
    if rule == "krum":
        return krum_coeff(d2, f)
    if rule == "multikrum":
        return multikrum_coeff(d2, f)
    if rule == "mda":
        return mda_coeff(d2, f)
    raise ValueError(f"{rule!r} is not a gram-space rule")
