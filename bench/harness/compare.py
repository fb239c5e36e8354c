"""The numbers that decide ``correct``: the program's first train steps
against the reference's, from the same seed and batches.

  loss_gap    largest |loss - ref| / |ref| over the steps (honest mean
              loss of each step)
  dnorm_gap   largest |norm - ref| / ref of the robust direction over the
              steps (before clipping) whose NNM choice the reference makes
              by a relative margin of at least NNM_TIE
  grad_gap    worst (worker, leaf) gap of the first gradient's norm, read
              from the momentum after step 1 (m = (1 - beta) g)
  change_gap  worst leaf gap of the norm of the weights' change over the
              steps

A leaf gap is |norm - ref| over the larger of the reference's norm of that
leaf and of the median leaf.  Leaves whose reference gradient is under a
thousandth of the median leaf's move by rounding alone and are left out
of both leaf gaps.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("loss_gap", "dnorm_gap", "grad_gap", "change_gap")
NEGLIGIBLE = 1e-3
#: Where the reference keeps its last neighbour by a smaller relative
#: margin of squared distance, the program's bf16 rounding can make the
#: other choice and the two directions differ by some percent (a margin of
#: 6.9e-4 did so on a TPU v5e); such steps are left out of dnorm_gap.
NNM_TIE = 5e-3


def _leaf_gaps(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    scale = np.maximum(want, np.median(want))
    return np.abs(got - want) / np.where(scale > 0, scale, 1.0)


def _aligned(prog: dict, ref: dict) -> dict:
    """Per-leaf arrays of both sides in the order of ``ref["paths"]``
    (``prog`` may give them in ``prog["paths"]`` order), and the mask of
    leaves that count."""
    order = [prog["paths"].index(p) for p in ref["paths"]]
    r_grad = np.asarray(ref["grad_norms"])
    leaf_grad = np.sqrt((r_grad ** 2).sum(axis=0))
    return {
        "p_grad": np.asarray(prog["grad_norms"])[:, order],
        "r_grad": r_grad,
        "p_change": np.asarray(prog["change_norms"])[order],
        "r_change": np.asarray(ref["change_norms"]),
        "keep": leaf_grad >= NEGLIGIBLE * np.median(leaf_grad),
    }


def numbers(prog: dict, ref: dict) -> dict:
    """{number: value} for the program's readings against the
    reference's."""
    a = _aligned(prog, ref)
    grad = np.stack([_leaf_gaps(a["p_grad"][w], a["r_grad"][w])
                     for w in range(a["r_grad"].shape[0])])
    change = _leaf_gaps(a["p_change"], a["r_change"])
    loss_p, loss_r = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    dn_p = np.asarray(prog["direction_norm"])
    dn_r = np.asarray(ref["direction_norm"])
    decided = np.asarray(ref["nnm_margin"]) >= NNM_TIE
    return {
        "loss_gap": float(np.max(np.abs(loss_p - loss_r) / np.abs(loss_r))),
        "dnorm_gap": float(np.max(np.where(decided,
                                           np.abs(dn_p - dn_r) / dn_r, 0.0))),
        "grad_gap": float(np.max(np.where(a["keep"], grad, 0.0))),
        "change_gap": float(np.max(np.where(a["keep"], change, 0.0))),
    }


def leaf_report(prog: dict, ref: dict, k: int = 3) -> list:
    """The ``k`` leaves with the widest change gap: [path, program's
    change norm, reference's, gap], widest first."""
    a = _aligned(prog, ref)
    gaps = np.where(a["keep"], _leaf_gaps(a["p_change"], a["r_change"]), 0.0)
    return [[ref["paths"][i], float(a["p_change"][i]),
             float(a["r_change"][i]), float(gaps[i])]
            for i in np.argsort(-gaps)[:k]]


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {number: {"value", "limit"}}): correct when every number
    is finite and within its limit."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
