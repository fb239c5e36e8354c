"""Theoretical quantities from the paper, as executable code.

Used by tests (property-checking (f, kappa)-robustness with the exact Table 1
coefficients) and by the convergence benchmarks (Theorem 1/2 error bounds).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Table 1 / Appendix 8.1 robustness coefficients (exact, incl. constants).
# ---------------------------------------------------------------------------

def kappa(rule: str, n: int, f: int) -> float:
    """Exact (f, kappa)-robustness coefficient proved in Appendix 8.1."""
    if rule == "average" and f == 0:
        return 0.0
    return _kappa_pos(rule, n, f)


def _kappa_pos(rule: str, n: int, f: int) -> float:
    if n <= 2 * f:
        raise ValueError("kappa undefined for n <= 2f")
    r = f / (n - 2 * f)
    if rule == "cwtm":
        # Prop. 2: 6f/(n-2f) (1 + f/(n-2f))
        return 6.0 * r * (1.0 + r)
    if rule == "krum":
        # Prop. 3: 6 (1 + f/(n-2f))
        return 6.0 * (1.0 + r)
    if rule in ("gm", "cwmed"):
        # Prop. 4/5: 4 (1 + f/(n-2f))^2
        return 4.0 * (1.0 + r) ** 2
    if rule == "autogm":
        # AutoGM's stationary point is a weighted GM with simplex weights
        # adapted toward inliers; its worst-case deviation is bounded by
        # the uniform-weight GM coefficient (Prop. 4 surrogate), which is
        # what the composed NNM∘AutoGM kappa-hat accounting uses.
        return 4.0 * (1.0 + r) ** 2
    if rule == "average":
        return 0.0
    raise ValueError(f"no proved kappa for rule {rule!r}")


def kappa_lower_bound(n: int, f: int) -> float:
    """Universal lower bound (Prop. 6): kappa >= f/(n-2f)."""
    return f / (n - 2 * f)


def nnm_kappa(base_kappa: float, n: int, f: int) -> float:
    """Lemma 1: F∘NNM is (f, kappa')-robust with kappa' <= 8f/(n-f)(kappa+1)."""
    return 8.0 * f / (n - f) * (base_kappa + 1.0)


def nnm_variance_factor(n: int, f: int) -> float:
    """Lemma 5: var(Y_S) + bias^2 <= [8f/(n-f)] var(X_S)."""
    return 8.0 * f / (n - f)


def bucketed_population(n: int, f: int, bucket_size: int | None = None
                        ) -> tuple[int, int]:
    """(n_buckets, f') after an s-sized bucketing stage.

    The population shrinks to ceil(n/s) while each Byzantine input
    contaminates at most one bucket, so f' = f (Karimireddy et al.,
    arXiv 2006.09365 — the paper's Observation 2 trade-off).  Raises when
    the reduced population can no longer tolerate f (n_buckets <= 2f):
    shrinking too aggressively destroys the robustness precondition."""
    from repro.core.bucketing import clamp_bucket_size, num_buckets
    s = clamp_bucket_size(n, bucket_size, f)
    n_b = num_buckets(n, s)
    if f > 0 and n_b <= 2 * f:
        raise ValueError(
            f"bucket_size={s} reduces n={n} to {n_b} buckets, which cannot "
            f"tolerate f={f} (need n_buckets > 2f)")
    return n_b, f


def composed_kappa(rule: str, n: int, f: int, pre: str | None = None, *,
                   hier: bool = False,
                   bucket_size: int | None = None) -> float:
    """Kappa of the composed pipeline [bucketing ->] pre -> rule.

    Lemma 1 for ``pre="nnm"`` (covers every base rule with a proved kappa,
    including the AutoGM surrogate); the bare Table 1 coefficient
    otherwise.  ``pre="bucketing"`` and ``hier=True`` both insert an
    s-sized bucketing stage (:func:`bucketed_population`): the downstream
    coefficients are evaluated at the REDUCED population (ceil(n/s), f) —
    hier composes with a further ``pre="nnm"`` stage on the reduced stack
    (bucketing -> NNM -> rule, the hierarchical-aggregation pipeline),
    which is where the s vs kappa trade-off lives: larger s shrinks the
    O(n^2) compute quadratically but inflates f/(n_b - 2f) and with it
    every Table 1 coefficient (see docs/perf.md for the table).
    """
    if pre == "bucketing":
        if hier:
            raise ValueError(
                "hier already inserts a bucketing stage; pre='bucketing' "
                "would bucket twice")
        n, f = bucketed_population(n, f, bucket_size)
        pre = None
    elif hier:
        n, f = bucketed_population(n, f, bucket_size)
    base = kappa(rule, n, f)
    if pre in (None, "none"):
        return base
    if pre == "nnm":
        return nnm_kappa(base, n, f)
    raise ValueError(f"no composed kappa for pre-aggregation {pre!r}")


#: Rules with a finite breakdown point under the paper's n > 2f adaptation.
ROBUST_RULES = frozenset({"krum", "multikrum", "gm", "autogm", "cwmed",
                          "cwtm", "mda", "meamed"})


def max_tolerable_f(rule: str, n: int, *, pre: str | None = None) -> int:
    """Largest Byzantine count f* the rule tolerates on n workers.

    Every robust rule in this repo is adapted (paper Appendix 8.1) to keep
    n - f rows and requires n > 2f, so f* = floor((n-1)/2) across the zoo;
    NNM composes at the same f (Lemma 1), leaving f* unchanged.  Plain
    averaging breaks down at a single Byzantine worker (f* = 0).
    """
    if pre not in (None, "none", "nnm", "bucketing"):
        raise ValueError(f"unknown pre-aggregation {pre!r}")
    if n < 1:
        raise ValueError(f"need n >= 1, got n={n}")
    if rule == "average":
        return 0
    if rule not in ROBUST_RULES:
        raise ValueError(f"no breakdown point for rule {rule!r}")
    return (n - 1) // 2


def breakdown_point(rule: str, n: int, f: int = 0, *,
                    pre: str | None = None) -> float:
    """Theoretical breakdown point f*/n of ``rule`` on n workers.

    The largest *fraction* of Byzantine workers under which
    (f, kappa)-robustness still holds — the asymptote the empirical
    collapse frontier (:mod:`repro.robustness.breakdown`) is swept toward.
    ``f`` is the current operating budget and is validated against the
    limit so misconfigured sweeps fail loudly.
    """
    fmax = max_tolerable_f(rule, n, pre=pre)
    if not 0 <= f <= fmax:
        raise ValueError(
            f"f={f} outside [0, {fmax}] = tolerable range of {rule!r} "
            f"(pre={pre!r}) on n={n} workers")
    return fmax / n


# ---------------------------------------------------------------------------
# Convergence bounds.
# ---------------------------------------------------------------------------

def dgd_bound(kappa_: float, g_sq: float, smooth_l: float, loss_gap: float,
              steps: int) -> float:
    """Theorem 1: ||grad L_H(theta_hat)||^2 <= 4 kappa G^2 + 4 L Delta / T."""
    return 4.0 * kappa_ * g_sq + 4.0 * smooth_l * loss_gap / steps


def dshb_bound(kappa_: float, g_sq: float, sigma_sq: float, smooth_l: float,
               loss_gap: float, n: int, f: int, steps: int) -> float:
    """Theorem 2 expected-error bound with the paper's explicit constants."""
    a1 = 36.0
    a2 = 6.0 * math.sqrt(max(loss_gap, 0.0))
    a3 = 1728.0 * smooth_l
    a4 = 288.0 * smooth_l
    a5 = 6.0 * smooth_l * a2 ** 2
    a_k = math.sqrt(a3 * kappa_ + a4 / (n - f))
    sigma = math.sqrt(sigma_sq)
    t = float(steps)
    bound = a1 * kappa_ * g_sq + a2 * a_k * sigma / math.sqrt(t) + a5 / t
    if a_k > 0:
        bound += a2 * a4 * sigma / (n * a_k * t ** 1.5)
    return bound


def dshb_hyperparams(smooth_l: float, loss_gap: float, kappa_: float,
                     sigma_sq: float, n: int, f: int, steps: int
                     ) -> tuple[float, float]:
    """Theorem 2's (learning rate, momentum beta) prescription."""
    a2 = 6.0 * math.sqrt(max(loss_gap, 1e-12))
    a3 = 1728.0 * smooth_l
    a4 = 288.0 * smooth_l
    a_k = math.sqrt(a3 * kappa_ + a4 / (n - f))
    sigma = math.sqrt(max(sigma_sq, 1e-12))
    gamma = min(1.0 / (24.0 * smooth_l), a2 / (2.0 * a_k * sigma * math.sqrt(steps)))
    beta = math.sqrt(max(0.0, 1.0 - 24.0 * gamma * smooth_l))
    return gamma, beta


def resilience_lower_bound(n: int, f: int, g_sq: float) -> float:
    """Prop. 1 / Appendix 12 explicit constant: eps >= f/(4(n-2f)) G^2."""
    return f / (4.0 * (n - 2 * f)) * g_sq


def tree_kappa_hat(agg, stack, n_honest, internals=None):
    """Paper Eq. (26) over worker-stacked pytrees, leaf-streamed in fp32.

    ``stack`` leaves carry a leading worker axis; the first ``n_honest``
    rows are the honest workers.  This is the shared estimator of the
    lockstep trainer, the fed server and the fleet lanes (all record it
    per round/step); :func:`empirical_kappa_hat` below is the
    single-(n, d)-stack form.  A python-int ``n_honest`` slices the honest
    rows; a TRACED one (a fleet lane's count) selects them by a mask
    (row < n_honest), so lanes with different Byzantine budgets share one
    compiled round.

    ``internals`` (taps support, see :mod:`repro.obs.taps`): when a dict
    is passed, the squared distance ``num`` and the per-leaf honest means
    are stashed (``"honest_sq_dist"`` / ``"honest_mean_leaves"``) so the
    health taps reuse this traversal instead of re-walking the stack.
    """
    num = jnp.zeros((), jnp.float32)
    den = jnp.zeros((), jnp.float32)
    static = isinstance(n_honest, int)
    if not static:
        cnt = jnp.maximum(n_honest.astype(jnp.float32), 1.0)
    for a, s in zip(jax.tree_util.tree_leaves(agg),
                    jax.tree_util.tree_leaves(stack)):
        if static:
            h = s[:n_honest].astype(jnp.float32)
            mbar = h.mean(axis=0)
        else:
            x = s.astype(jnp.float32)
            n = x.shape[0]
            w = (jnp.arange(n) < n_honest).astype(jnp.float32)
            wl = w.reshape((-1,) + (1,) * (x.ndim - 1))
            mbar = (x * wl).sum(axis=0) / cnt
        if internals is not None:
            internals.setdefault("honest_mean_leaves", []).append(mbar)
        num += jnp.sum((a.astype(jnp.float32) - mbar) ** 2)
        if static:
            den += jnp.mean(jnp.sum((h - mbar).reshape(n_honest, -1) ** 2,
                                    axis=1))
        else:
            sq = jnp.sum(((x - mbar) ** 2).reshape(n, -1), axis=1)
            den += (sq * w).sum() / cnt
    if internals is not None:
        internals["honest_sq_dist"] = num
    return jnp.sqrt(num / (den + 1e-20))


def empirical_kappa_hat(agg_out, stack, honest_idx=None):
    """kappa_hat_t of Eq. (26): ||R - mbar||^2 / mean_i ||m_i - mbar||^2.

    `stack` are the honest rows (or the full stack with `honest_idx`).
    Returns the *squared* ratio's square root companion per the paper's
    figure (they plot kappa_hat, we return kappa_hat^2's sqrt = kappa_hat).
    """
    h = stack if honest_idx is None else stack[honest_idx]
    h = h.astype(jnp.float32)
    mbar = h.mean(axis=0)
    num = jnp.sum((agg_out.astype(jnp.float32) - mbar) ** 2)
    den = jnp.mean(jnp.sum((h - mbar) ** 2, axis=-1)) + 1e-20
    return jnp.sqrt(num / den)
