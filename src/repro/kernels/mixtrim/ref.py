"""Pure-jnp forms of the coordinate-wise rules: the oracle of the fused
NNM-mix + coordinate-wise-trim kernel, and the rules the xla backend (and
meamed, which has no kernel, on every backend) runs.

A trim count ``f`` is a python int or a TRACED int32 scalar (a fleet
lane's Byzantine budget).  An int keeps a static slice of the sorted
ranks; a tracer weights every rank by a mask, so one program serves every
f.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _ranks(xs):
    """Row index along axis 0, broadcastable against ``xs``."""
    return jnp.arange(xs.shape[0]).reshape((-1,) + (1,) * (xs.ndim - 1))


def _trimmed_mean(xs, f):
    """Mean over axis 0 of ranks f..n-f-1 of the ascending stack ``xs``."""
    n = xs.shape[0]
    if isinstance(f, int):
        return jax.lax.slice_in_dim(xs, f, n - f, axis=0).mean(axis=0)
    f = jnp.asarray(f, jnp.int32)
    i = _ranks(xs)
    keep = ((i >= f) & (i < n - f)).astype(jnp.float32)
    return (xs * keep).sum(axis=0) / jnp.maximum(
        (n - 2 * f).astype(jnp.float32), 1.0)


def mixtrim_ref(x, m, f, mode: str = "trim"):
    """Fused Y = M @ X followed by a coordinate-wise robust reduction.

    Args:
      x: (n, d) worker stack, or any (n, ...) one (a leaf view).
      m: (n, n) mixing matrix, or None for no NNM (the mix is skipped).
      f: trim count, a python int or a traced int32 scalar.
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
        if isinstance(f, int) and f == 0:
            return y.mean(axis=0)
        return _trimmed_mean(ys, f)
    if mode == "med":
        if n % 2 == 1:
            return ys[n // 2]
        return 0.5 * (ys[n // 2 - 1] + ys[n // 2])
    raise ValueError(mode)


def coordinate_rule(x, rule: str, f, sorted_out: list | None = None):
    """cwmed, cwtm or meamed of a worker-stacked (n, ...) array along its
    worker axis, in fp32.

    ``sorted_out``: a list that receives cwtm's sorted stack, so the
    health taps reuse the sort instead of re-emitting it."""
    n = x.shape[0]
    x = x.astype(jnp.float32)
    if rule == "cwmed":
        return jnp.median(x, axis=0)
    if rule == "cwtm":
        if isinstance(f, int) and f == 0:
            return x.mean(axis=0)
        xs = jnp.sort(x, axis=0)
        if sorted_out is not None:
            sorted_out.append(xs)
        return _trimmed_mean(xs, f)
    if rule == "meamed":
        med = jnp.median(x, axis=0, keepdims=True)
        order = jnp.argsort(jnp.abs(x - med), axis=0)
        xs = jnp.take_along_axis(x, order, axis=0)
        if isinstance(f, int):
            return jax.lax.slice_in_dim(xs, 0, n - f, axis=0).mean(axis=0)
        keep = (_ranks(xs) < n - f).astype(jnp.float32)
        return (xs * keep).sum(axis=0) / jnp.maximum(
            (n - f).astype(jnp.float32), 1.0)
    raise ValueError(rule)
