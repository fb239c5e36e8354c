"""The yardstick's arithmetic: chip peaks, model FLOPs of a train step,
and the least operations and bytes of the aggregation kernels.

Rooflines count the algorithm's least traffic, not a kernel's tiles, so
that a re-tiled or fused kernel is measured against the same work.
"""
from __future__ import annotations

import math

#: Published peaks per chip, keyed by ``device_kind``.  Source: Google
#: Cloud documentation, "TPU v5e" (bf16 matrix peak, HBM bandwidth).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}

#: Bytes per value of the worker stack the kernels read (float32).
STACK_BYTES = 4


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(f"no published peaks for device {device_kind!r}; "
                         f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def leaf_sizes(ref, sizes: dict) -> dict:
    """{leaf path: number of values} of a configuration."""
    return {p: math.prod(shape)
            for p, (shape, _) in ref.param_specs(sizes).items()}


def model_flops_per_step(ref, sizes: dict, traffic: dict) -> float:
    """Forward and backward FLOPs of all n workers' rows in one step: 2
    per weight per position it multiplies, plus attention's score and
    value products, times 3 for the backward pass.  Nothing recomputed
    counts."""
    count = leaf_sizes(ref, sizes)
    fwd = sum(2.0 * count[p] * pos
              for p, pos in ref.matmul_positions(sizes, traffic).items())
    fwd += ref.attention_flops_per_row(sizes, traffic)
    rows = int(traffic["workers"]) * int(traffic["batch"])
    return 3.0 * fwd * rows


def gram_cost(n: int, widths: list) -> tuple[float, float]:
    """(FLOPs, bytes) of the n x n Gram of every leaf's (n, d_i) stack:
    2 n^2 d_i operations, the stack read once, one n x n output each."""
    d = sum(widths)
    return (2.0 * n * n * d,
            float(STACK_BYTES * n * d + 4 * n * n * len(widths)))


def mixtrim_cost(n: int, widths: list, mix: bool) -> tuple[float, float]:
    """(FLOPs, bytes) of the fused mix + trim: the mix M @ X costs 2 n^2 d
    operations (none without NNM); the stack is read once and the float32
    direction written once."""
    d = sum(widths)
    return (2.0 * n * n * d if mix else 0.0,
            float(STACK_BYTES * n * d + 4 * d))


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bytes_per_s"])


#: What each kernel's device ops are called in a trace: the HLO
#: instruction takes the name of the jitted kernel wrapper.
KERNELS = {
    "gram": ("%gram_pallas",),
    "mixtrim": ("%mixtrim_pallas",),
}
