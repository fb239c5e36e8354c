"""In-round gradient quarantine: graceful degradation for faulty workers.

The robust aggregators tolerate up to f *adversarial* rows, but a merely
*faulty* worker — nan from bad data, inf from fp overflow, a norm-exploded
update after a divergent local solve — burns part of that budget on
behavior that is trivially detectable.  The guard screens the worker stack
INSIDE the compiled round, before aggregation:

* non-finite rows (any nan/inf entry in any leaf) are always quarantined;
* rows whose global update norm exceeds ``norm_factor`` times the median
  finite-row norm are quarantined (0 disables this screen);
* quarantined rows are replaced by the coordinate-wise lower median of the
  surviving rows — an inlier by construction, so the aggregator sees a
  well-formed stack and the round completes with finite loss.

Quarantined rows must be counted against the f budget by the operator:
replacement makes the row harmless to *this* round, but a worker that can
force quarantine at will controls its replacement (an inlier, i.e. a
benign vote) and an adversary simulating "faulty" behavior is still an
adversary.  The counts are therefore surfaced everywhere — the round's
metrics (``quarantined_count``), :class:`repro.obs.taps.HealthTaps`
(``quarantined_count`` / ``quarantine_mask_honest`` / ``quarantine_mask_byz``)
and ``obs.runtime`` ``robustness.quarantine`` events — see
docs/robustness.md.

Everything is static-shape mask math (sorts with +/-inf sentinels, traced
counts), so the guard runs unchanged on the static, traced-f, and vmapped
fleet paths, and is a *bitwise no-op* on the stack when no row trips a
screen.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

Array = jax.Array
PyTree = Any


@dataclasses.dataclass(frozen=True)
class QuarantineConfig:
    """Static guard description (jit-key and fleet bucket-key material).

    Attributes:
      norm_factor: quarantine rows whose global update norm exceeds this
        multiple of the median finite-row norm; 0.0 disables the norm
        screen (non-finite screening is always on).
    """

    norm_factor: float = 10.0

    def __post_init__(self):
        if self.norm_factor < 0:
            raise ValueError(f"norm_factor must be >= 0, got "
                             f"{self.norm_factor}")


def quarantine_stack(tree: PyTree, cfg: QuarantineConfig
                     ) -> tuple[PyTree, dict]:
    """Screen a worker-stacked pytree; returns (screened tree, info).

    ``info`` is ``{"mask": (n,) float32 (1 = quarantined), "count": int32}``
    — pure side-outputs: when the mask is all-zero the returned tree is
    bit-for-bit the input (replacement goes through ``jnp.where`` with the
    original rows on the taken branch).
    """
    leaves = jax.tree_util.tree_leaves(tree)
    treedef = jax.tree_util.tree_structure(tree)
    n = leaves[0].shape[0]

    finite = jnp.ones((n,), bool)
    sq = jnp.zeros((n,), jnp.float32)
    for leaf in leaves:
        h = leaf.astype(jnp.float32).reshape(n, -1)
        ok = jnp.isfinite(h)
        finite = finite & ok.all(axis=1)
        # Sanitized accumulation: a non-finite row still needs a finite sq
        # so the median-of-finite-rows sort below stays well-defined.
        sq = sq + (jnp.where(ok, h, 0.0) ** 2).sum(axis=1)

    bad = ~finite
    if cfg.norm_factor:
        srt = jnp.sort(jnp.where(finite, sq, jnp.inf))
        cnt = finite.astype(jnp.int32).sum()
        med = jnp.take(srt, jnp.maximum((cnt - 1) // 2, 0))
        # Squared-space comparison; med = +inf when no row is finite, which
        # makes the norm screen vacuous (everything is quarantined anyway).
        bad = bad | (finite & (sq > cfg.norm_factor ** 2 * med))

    keep = ~bad
    kept = keep.astype(jnp.int32).sum()
    mid = jnp.maximum((kept - 1) // 2, 0)

    def replace(xs):
        out_leaves = []
        for leaf in xs:
            x = leaf.astype(jnp.float32)
            sel = keep.reshape((-1,) + (1,) * (x.ndim - 1))
            # Coordinate-wise lower median of the kept rows: +inf sentinels
            # push quarantined rows past the traced midpoint index.
            ys = jnp.sort(jnp.where(sel, x, jnp.inf), axis=0)
            fallback = jnp.take(ys, mid, axis=0)
            fallback = jnp.where(jnp.isfinite(fallback), fallback, 0.0)
            out = jnp.where(sel, x, fallback)
            out_leaves.append(out.astype(leaf.dtype))
        return out_leaves

    # The replacement (a per-coordinate sort of every leaf) only runs on
    # rounds where a screen actually tripped: the common clean round takes
    # the identity branch — trivially bitwise AND skipping the sort cost
    # (the >= 0.9x guard_overhead_ratio gate).  Under vmap (fleet lanes)
    # cond lowers to both-branches select, which is just the unconditional
    # replacement this code used to do.
    out_leaves = jax.lax.cond(bad.any(), replace, lambda xs: list(xs),
                              leaves)

    info = {"mask": bad.astype(jnp.float32),
            "count": bad.astype(jnp.int32).sum()}
    return jax.tree_util.tree_unflatten(treedef, out_leaves), info
