"""In-scan robustness health taps: per-round aggregator diagnostics.

The paper's mechanism is *mixing* (NNM, Alg. 2): honest workers absorb
Byzantine influence by averaging their n-f nearest neighbors, and the
robust output should track the honest mean up to the heterogeneity floor.
After the round loop compiled into single scan programs (PR 5) none of
that is visible anymore — so :func:`health_taps` computes a small pytree
of diagnostics **inside** the compiled round, from quantities the hot
path already derives:

* ``dist_honest`` — ``||R - mean(honest)||``, the quantity Theorem 1
  bounds by ``kappa' G^2`` (the taps are its empirical left-hand side);
* ``cos_honest`` — cosine of the robust output vs the honest mean
  direction (sign flips under a successful attack);
* ``neighbor_count`` — per worker j, how many NNM rows selected j as a
  neighbor (paper Alg. 2's selection structure; Byzantine workers that
  stay "indistinguishable" keep near-honest counts);
* ``mix_mass`` — per-worker column mass of the row-stochastic NNM matrix
  M, normalized to sum to 1: worker j's share of the total mixing
  weight.  ``byz_mix_mass`` / ``honest_mix_mass`` split that mass by the
  honest-first row convention — byz_mix_mass is exactly how much of the
  mixed stack the adversary controls;
* ``trim_frac`` — for cwtm (and NNM+cwtm = mixtrim), the fraction of
  coordinates on which worker row i lands in the trimmed tails (value
  outside the kept band ``[sorted[f], sorted[n-f-1]]`` per coordinate —
  identical to the rank criterion whenever coordinate values are
  distinct, and derived from the SAME sorted stack cwtm consumes).

Taps are **pure side-outputs**: plain jnp, never feeding back into the
model state, so a tapped run stays bit-for-bit equal to an untapped run
(tested).  They ride the existing scan-output metrics transfer — zero
extra host round-trips.  The heavy intermediates (NNM matrix, mixed
stack, cwtm's sorted stacks) are NOT recomputed: the aggregation stashes
them into an ``internals`` dict (see ``robust_aggregate``) and the taps
reuse them outright, leaving only O(n^2 + nD) reductions of new work.
(Relying on XLA CSE to deduplicate a recomputation is not enough —
inside ``lax.scan`` bodies the duplicated NNM construction fuses
per-consumer before CSE can merge the dominant sort/dot ops; measured at
~2x round cost.)  On the Pallas backends the fused mixtrim kernel never
materializes the mixed/sorted stack, so trim taps there pay one extra
leaf-streamed mix + sort pass (see docs/observability.md for the
overhead model — the ≥0.9x rounds/sec CI gate keeps the XLA path
honest).

``f`` and ``n_honest`` are python ints, or TRACED scalars on the fleet
lanes (rank-mask NNM, gathered trim thresholds), so one compiled tapped
round serves lanes with different Byzantine budgets.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import gram as gramlib
from repro.core import robust as robustlib

PyTree = Any
Array = jax.Array

_EPS = 1e-20


class HealthTaps(NamedTuple):
    """Per-round robustness diagnostics (a pytree: rides scan outputs).

    Fields whose precondition is not met (no NNM preaggregation, not a
    trim rule) are ``None`` — NamedTuple ``None`` entries are empty
    subtrees under jax, so the static tap structure is decided at trace
    time and costs nothing when absent."""
    dist_honest: Any                        # scalar ||R - honest mean||
    cos_honest: Any                         # scalar cos(R, honest mean)
    neighbor_count: Optional[Any] = None    # (n,) NNM selections of worker j
    mix_mass: Optional[Any] = None          # (n,) share of total mix weight
    byz_mix_mass: Optional[Any] = None      # scalar, sum over byz rows
    honest_mix_mass: Optional[Any] = None   # scalar, sum over honest rows
    trim_frac: Optional[Any] = None         # (n,) trimmed-coordinate frac
    # Quarantine-guard taps (present when the round runs with a
    # repro.robustness.guard screen): how many rows the guard replaced,
    # split by the honest-first row convention — quarantined *honest* rows
    # are faults the budget must absorb, quarantined byz rows are attacks
    # the guard already disarmed.
    quarantined_count: Optional[Any] = None       # scalar, replaced rows
    quarantine_mask_honest: Optional[Any] = None  # (n,) quarantined & honest
    quarantine_mask_byz: Optional[Any] = None     # (n,) quarantined & byz

    def to_dict(self) -> dict:
        """Present fields only — the demux/history view."""
        return {k: v for k, v in self._asdict().items() if v is not None}


TAP_FIELDS = HealthTaps._fields


def health_taps(stack: PyTree, aggregate: PyTree, *, n_honest, f,
                rule: str, pre: Optional[str],
                internals: Optional[dict] = None,
                quarantine: Optional[dict] = None) -> HealthTaps:
    """Compute the taps for one round.

    Args:
      stack: the post-attack worker-stacked pytree (leading axis n) the
        aggregator consumed.
      aggregate: the robust output pytree (worker axis removed).
      n_honest: honest row count (rows are honest-first; Python int, or
        traced int32).
      f: the aggregator's Byzantine budget (Python int, or traced int32).
      rule / pre: the AggregatorSpec fields that decide which taps exist
        (static — tap structure is trace-time).
      internals: the dict ``robust_aggregate`` filled when the caller
        passed one (``mix_matrix`` / ``mixed`` / ``sorted_leaves``) — the
        taps then reuse those intermediates outright and add only O(n^2 +
        nD) reductions.  Without it (standalone use) the NNM matrix,
        mixed stack, and sort are recomputed from ``stack``.
      quarantine: the guard's info dict (``{"mask", "count"}``, see
        :func:`repro.robustness.guard.quarantine_stack`) when the round
        screened the stack — fills the ``quarantined_*`` taps.

    NNM taps need ``pre == "nnm"``; trim taps need ``rule == "cwtm"``
    with pre in (None, "nnm") — under pre="bucketing" the trim acts on
    the bucketed stack, so per-worker ranks on the raw stack would not
    describe what the rule did, and the taps stay None.
    """
    internals = internals if internals is not None else {}
    leaves = jax.tree_util.tree_leaves(stack)
    r_leaves = jax.tree_util.tree_leaves(aggregate)
    n = leaves[0].shape[0]

    w = (jnp.arange(n) < n_honest).astype(jnp.float32)      # honest-first
    cnt = jnp.maximum(jnp.asarray(n_honest, jnp.float32), 1.0)

    # dist/cos accumulate leaf by leaf — no (n, D) concatenation copy.
    # When the kappa-hat estimator already walked the stack this round
    # (track_kappa_hat, the default), its per-leaf honest means and
    # squared distance are reused outright (see tree_kappa_hat).
    hm_leaves = internals.get("honest_mean_leaves")
    d_acc = jnp.float32(0.0)
    dot_acc = jnp.float32(0.0)
    nr_acc = jnp.float32(0.0)
    nh_acc = jnp.float32(0.0)
    for i, (leaf, r_leaf) in enumerate(zip(leaves, r_leaves)):
        r = r_leaf.reshape(-1).astype(jnp.float32)
        if hm_leaves is not None:
            hm = hm_leaves[i].reshape(-1)
        else:
            x = leaf.reshape(n, -1).astype(jnp.float32)
            hm = (x * w[:, None]).sum(axis=0) / cnt
            diff = r - hm
            d_acc = d_acc + jnp.sum(diff * diff)
        dot_acc = dot_acc + jnp.sum(r * hm)
        nr_acc = nr_acc + jnp.sum(r * r)
        nh_acc = nh_acc + jnp.sum(hm * hm)
    sq = internals.get("honest_sq_dist")
    dist = jnp.sqrt(sq if sq is not None else d_acc)
    cos = dot_acc / (jnp.sqrt(nr_acc) * jnp.sqrt(nh_acc) + _EPS)

    taps: dict[str, Any] = {"dist_honest": dist, "cos_honest": cos}

    if quarantine is not None:
        qm = quarantine["mask"].astype(jnp.float32)
        taps["quarantined_count"] = quarantine["count"].astype(jnp.float32)
        taps["quarantine_mask_honest"] = qm * w
        taps["quarantine_mask_byz"] = qm * (1.0 - w)

    m = None
    if pre == "nnm":
        m = internals.get("mix_matrix")
        if m is None:       # standalone: rebuild from the stack's gram
            g = robustlib.tree_gram(stack)
            d2 = gramlib.pdist_sq_from_gram(g)
            m = gramlib.nnm_matrix(d2, f)
        taps["neighbor_count"] = (m > 0).astype(jnp.float32).sum(axis=0)
        col = m.sum(axis=0) / float(n)      # row-stochastic: sums to 1
        taps["mix_mass"] = col
        taps["byz_mix_mass"] = (col * (1.0 - w)).sum()
        taps["honest_mix_mass"] = (col * w).sum()

    if rule == "cwtm" and pre in (None, "nnm"):
        if isinstance(f, int) and f == 0:
            # cwtm with f=0 is a plain mean: nothing is ever trimmed (and
            # the aggregation emitted no sort to reuse).
            taps["trim_frac"] = jnp.zeros((n,), jnp.float32)
            return HealthTaps(**taps)
        mixed = internals.get("mixed")
        if mixed is None:
            mixed = stack if m is None else robustlib.tree_mix(stack, m)
        mixed_leaves = jax.tree_util.tree_leaves(mixed)
        sorted_leaves = internals.get("sorted_leaves")
        if sorted_leaves is None:
            sorted_leaves = [jnp.sort(leaf.astype(jnp.float32), axis=0)
                             for leaf in mixed_leaves]
        fa = jnp.asarray(f, jnp.int32)
        trim_cnt = jnp.zeros((n,), jnp.float32)
        total = 0
        for leaf, xs in zip(mixed_leaves, sorted_leaves):
            y = leaf.reshape(n, -1).astype(jnp.float32)
            ys = xs.reshape(n, -1)
            lo = jnp.take(ys, fa, axis=0)           # f-th smallest: kept
            hi = jnp.take(ys, n - 1 - fa, axis=0)   # f-th largest: kept
            trimmed = ((y < lo[None, :]) | (y > hi[None, :]))
            trim_cnt = trim_cnt + trimmed.astype(jnp.float32).sum(axis=1)
            total += y.shape[1]
        taps["trim_frac"] = trim_cnt / float(total)

    return HealthTaps(**taps)
