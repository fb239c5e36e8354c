"""The plain reference of a robust D-SHB step, leaf by leaf.

Per step: every worker's loss and gradient of its own batch (the
configuration's reference model, float32), worker momentum
m_i <- beta m_i + (1 - beta) g_i, the ALIE attack on the last f rows
(honest mean plus one coordinate-wise standard deviation), nearest-
neighbour mixing (each row averages its n - f nearest rows, by squared
distance over the whole model), the coordinate-wise trimmed mean that
drops f values at each end, and SGD with the update clipped to a global
norm, applied to weights held in bfloat16.

It imports nothing of the program and makes its own weights from the
seed.  Only one worker's gradient and one leaf's (n, d) stack are alive
beside the momenta at a time, so it fits beside nothing else on the chip
at the configurations' full widths.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import ref_layers as L

HIGHEST = jax.lax.Precision.HIGHEST


_JITTED: dict = {}


def _once(name: str, ref, sizes: dict, make):
    """One jitted function per name, reference and configuration in a
    process, so that runs from other seeds reuse its compile."""
    k = (name, ref.__name__, repr(sorted(sizes.items())))
    if k not in _JITTED:
        _JITTED[k] = jax.jit(make())
    return _JITTED[k]


def init_params(ref, sizes: dict, key: jax.Array) -> dict:
    """{path: bfloat16 weight}, in one jitted call."""
    def make():
        specs = ref.param_specs(sizes)
        return lambda k: {p: L.init_leaf(k, p, shape, kind)
                          for p, (shape, kind) in specs.items()}
    return _once("init", ref, sizes, make)(key)


@functools.partial(jax.jit, static_argnames=("f",))
def _alie(leaves: list, f: int) -> jax.Array:
    """(n, d) stack of one leaf with its last f rows replaced by the honest
    mean plus one (population) standard deviation."""
    x = jnp.stack([l.reshape(-1) for l in leaves])
    honest = x[: x.shape[0] - f]
    byz = honest.mean(0) + honest.std(0)
    return jnp.concatenate([honest, jnp.broadcast_to(byz, (f,) + byz.shape)])


@jax.jit
def _gram(x: jax.Array) -> jax.Array:
    return jnp.einsum("nd,md->nm", x, x, precision=HIGHEST)


def nnm_matrix(gram: np.ndarray, f: int) -> tuple[np.ndarray, float]:
    """Row i averages the n - f rows nearest to row i (itself included);
    ties go to the lower index.  Also returns the margin of the choice:
    the smallest, over rows, relative gap between the squared distance of
    the first row left out and of the last row kept."""
    n = gram.shape[0]
    diag = np.diag(gram)
    d2 = np.maximum(diag[:, None] - 2 * gram + diag[None, :], 0.0)
    m = np.zeros((n, n))
    margin = np.inf
    for i in range(n):
        order = np.argsort(d2[i], kind="stable")
        m[i, order[: n - f]] = 1.0 / (n - f)
        out, kept = d2[i, order[n - f]], d2[i, order[n - f - 1]]
        margin = min(margin, (out - kept) / max(out, 1e-300))
    return m, float(margin)


@functools.partial(jax.jit, static_argnames=("f",))
def _trimmed(x: jax.Array, m: Optional[jax.Array], f: int) -> jax.Array:
    """Coordinate-wise mean of the middle n - 2f values of M @ X."""
    if m is not None:
        x = jnp.einsum("mn,nd->md", m, x, precision=HIGHEST)
    xs = jnp.sort(x, axis=0)
    return xs[f: x.shape[0] - f].mean(0)


@functools.partial(jax.jit, donate_argnums=0, static_argnames=("beta",))
def _momentum(m: dict, g: dict, beta: float) -> dict:
    return {k: beta * m[k] + (1 - beta) * g[k] for k in m}


@jax.jit
def _sgd(p: dict, d: dict, lr, scale) -> dict:
    """The step on bfloat16 weights: p - lr * d * scale in float32,
    rounded to bfloat16 (the output's dtype, so the rounding stays)."""
    return {k: (p[k].astype(jnp.float32) - lr * (d[k] * scale))
            .astype(jnp.bfloat16) for k in p}


@jax.jit
def _as_f32(p: dict) -> dict:
    return {k: v.astype(jnp.float32) for k, v in p.items()}


@jax.jit
def _norms(tree: dict) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(v))) for v in tree.values()])


@jax.jit
def _diff_norms(a: dict, b: dict) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        a[k].astype(jnp.float32) - b[k].astype(jnp.float32)))) for k in a])


def aggregate(momenta: list, traffic: dict) -> tuple[dict, float]:
    """The robust direction of the momenta of all n workers, leaf by
    leaf: ALIE on the last f rows, NNM when the mix asks for it, then the
    coordinate-wise trimmed mean.  Also returns the NNM choice's margin
    (infinite without NNM)."""
    f = int(traffic["byz"])
    pre, _, rule = traffic["agg"].rpartition("+")
    if rule != "cwtm" or pre not in ("", "nnm") or traffic["attack"] != "alie":
        raise SystemExit(f"the reference has no {traffic['attack']} / "
                         f"{traffic['agg']} step")
    keys = list(momenta[0])
    mix, margin = None, float("inf")
    if pre == "nnm":
        g = sum(np.asarray(_gram(_alie([m[k] for m in momenta], f)),
                           np.float64) for k in keys)
        mix, margin = nnm_matrix(g, f)
        mix = jnp.asarray(mix, jnp.float32)
    out = {}
    for k in keys:
        x = _alie([m[k] for m in momenta], f)
        out[k] = _trimmed(x, mix, f).reshape(momenta[0][k].shape)
    return out, margin


def half(batch: dict) -> dict:
    """One worker's batch with half of it left out: the first half of its
    rows, or, for a single row, the first half of its tokens and labels
    (the loss is then the mean over the rest)."""
    if batch["tokens"].shape[0] >= 2:
        return {k: v[: v.shape[0] // 2] for k, v in batch.items()}
    cut = batch["tokens"].shape[1] // 2
    return dict(batch, tokens=batch["tokens"][:, :cut],
                labels=batch["labels"][:, :cut])


def run(ref, sizes: dict, traffic: dict, key: jax.Array, batches: list,
        *, mode: str = "fp32", half_batch: bool = False) -> dict:
    """The reference's readings over ``len(batches)`` steps from the
    seed's weights: each step's honest mean loss and direction norm, the
    norm of every worker's first gradient on every leaf, and the norm of
    every leaf's change after the last step.

    ``mode`` is the precision of the model's products ("fp32" or the
    "fp8" control); ``half_batch`` plants a fault: every worker's loss
    takes only half of its batch (see :func:`half`)."""
    n, f = int(traffic["workers"]), int(traffic["byz"])
    beta, lr = float(traffic["beta"]), float(traffic["lr"])
    clip = float(traffic["clip"])
    params = start = init_params(ref, sizes, key)
    mm = L.matmul(mode)
    grad_fn = _once("grad_" + mode, ref, sizes, lambda: jax.value_and_grad(
        lambda p, b: ref.loss(p, b, mm, sizes)))
    momenta: list = [None] * n
    out = {"paths": list(params), "loss": [], "direction_norm": [],
           "nnm_margin": []}
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches):
            losses, grad_norms = [], []
            p32 = _as_f32(params)
            for w in range(n):
                b = {k: jnp.asarray(v[w]) for k, v in batch.items()}
                if half_batch:
                    b = half(b)
                loss, grad = grad_fn(p32, b)
                losses.append(float(loss))
                if t == 0:
                    grad_norms.append(np.asarray(_norms(grad), np.float64))
                    momenta[w] = jax.tree_util.tree_map(jnp.zeros_like, grad)
                momenta[w] = _momentum(momenta[w], grad, beta)
                del grad
            del p32
            if t == 0:
                out["grad_norms"] = np.stack(grad_norms)
            direction, margin = aggregate(momenta, traffic)
            dnorm = float(jnp.sqrt(sum(jnp.sum(jnp.square(v))
                                       for v in direction.values())))
            scale = min(1.0, clip / (dnorm + 1e-12))
            params = _sgd(params, direction, jnp.float32(lr),
                          jnp.float32(scale))
            del direction
            out["loss"].append(float(np.mean(losses[: n - f])))
            out["direction_norm"].append(dnorm)
            out["nnm_margin"].append(margin)
    out["change_norms"] = np.asarray(_diff_norms(params, start), np.float64)
    return out
