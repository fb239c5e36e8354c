"""Pure-jnp oracle for the fused NNM-mix + coordinate-wise-trim kernel."""
from __future__ import annotations

import jax.numpy as jnp


def mixtrim_ref(x, m, f: int, mode: str = "trim"):
    """Fused Y = M @ X followed by a coordinate-wise robust reduction.

    Args:
      x: (n, d) worker stack, or any (n, ...) one (a leaf view).
      m: (n, n) mixing matrix, or None for no NNM (the mix is skipped).
      f: trim count.
      mode: "trim" (CWTM over the mixed stack) or "med" (CWMed).

    Returns: (d,) aggregated vector, fp32; x.shape[1:] for a leaf view.
    """
    if x.ndim != 2:
        return mixtrim_ref(x.reshape(x.shape[0], -1), m, f,
                           mode).reshape(x.shape[1:])
    n = x.shape[0]
    y = x.astype(jnp.float32) if m is None \
        else m.astype(jnp.float32) @ x.astype(jnp.float32)
    ys = jnp.sort(y, axis=0)
    if mode == "trim":
        if f == 0:
            return y.mean(axis=0)
        return ys[f : n - f].mean(axis=0)
    if mode == "med":
        if n % 2 == 1:
            return ys[n // 2]
        return 0.5 * (ys[n // 2 - 1] + ys[n // 2])
    raise ValueError(mode)


def mixtrim_dyn_ref(x, m, f, mode: str = "trim"):
    """`mixtrim_ref` with a traced trim count: rank-mask selection over the
    sorted mixed stack (the `_tree_coordinate_rule_dyn` arithmetic, so the
    dynamic kernel and the fleet's jnp path share one oracle)."""
    if x.ndim != 2:
        return mixtrim_dyn_ref(x.reshape(x.shape[0], -1), m, f,
                               mode).reshape(x.shape[1:])
    n = x.shape[0]
    f = jnp.asarray(f, jnp.int32)
    y = x.astype(jnp.float32) if m is None \
        else m.astype(jnp.float32) @ x.astype(jnp.float32)
    ys = jnp.sort(y, axis=0)
    if mode == "trim":
        i = jnp.arange(n)[:, None]
        keep = ((i >= f) & (i < n - f)).astype(jnp.float32)
        denom = jnp.maximum((n - 2 * f).astype(jnp.float32), 1.0)
        return (ys * keep).sum(axis=0) / denom
    if mode == "med":
        if n % 2 == 1:
            return ys[n // 2]
        return 0.5 * (ys[n // 2 - 1] + ys[n // 2])
    raise ValueError(mode)
