"""The expert layer's share of a trace, and the least work of its held
experts' products.

The program tags its expert layer's operations with a frontend attribute
of their own, ``moe_part="route"`` or ``moe_part="experts"``
(``repro.obs.stages.moe_part``), beside the ``robust_stage`` of the step
around them; XLA keeps it on the fusions and custom calls it builds from
them (the grouped products lower to custom calls of their own), and the
text of a device op in a TPU profile holds it.  A trace of a program
without the tag reads None.
"""
from __future__ import annotations

from typing import Optional

#: What the text of a tagged op holds, up to the part's name.
TAG = 'moe_part="'
#: Bytes per value of the expert weights and activations (bfloat16).
VALUE_BYTES = 2
#: Passes over the products in a train step: forward, the backward to the
#: inputs and the backward to the weights.
PASSES = 3


def part_ms(ctx, part: str) -> Optional[float]:
    """Device ms per step of the non-parent ops tagged ``part``, custom
    calls included, per device; None when no op of the trace carries an
    expert-layer tag."""
    ops = [o for o in ctx.trace.ops if not o.parent]
    if not any(TAG in o.text for o in ops):
        return None
    tag = f'{TAG}{part}"'
    ns = sum(o.dur_ns for o in ops if tag in o.text)
    return 1e3 * ns * 1e-9 / ctx.trace.devices / ctx.steps


def expected_pairs(sizes: dict, traffic: dict) -> float:
    """(token, held expert) pairs of one step under uniform routing: every
    worker's tokens, k experts each, H of E held, in every layer."""
    tokens = (int(traffic["workers"]) * int(traffic["batch"])
              * int(traffic["seq"]))
    held = sizes.get("experts_held") or sizes["num_experts"]
    return (tokens * sizes["experts_per_token"] * held / sizes["num_experts"]
            * sizes["num_layers"])


def expert_cost(sizes: dict, traffic: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of one step's held-expert products: 3 passes x 2 x 3
    d ff per expected pair; each pass reads the held experts' three
    weights once and the pairs' rows in and out (d wide)."""
    d, ff = sizes["d_model"], sizes["d_ff"]
    held = sizes.get("experts_held") or sizes["num_experts"]
    pairs = expected_pairs(sizes, traffic)
    weights = sizes["num_layers"] * held * 3 * d * ff
    return (PASSES * 2.0 * 3 * d * ff * pairs,
            float(PASSES * VALUE_BYTES * (weights + 2 * pairs * d)))
