"""Device time per stage of the robust step, from the program's stage
tags.

The program traces each stage of its train step (backward, momentum,
attack, aggregate, kappa, optimizer, taps) under a frontend attribute
``robust_stage="<stage>"`` (``repro.obs.stages``).  XLA keeps it on the
fusions and custom calls it builds from the tagged operations, and the
text of a device op in a TPU profile holds it.  These sums split
``xla_ms``: each counts the ops outside the Pallas kernels, like
``xla_ms``, so the stages and the untagged rest add up to it, up to op
overlap.  A trace of a program without tags reads None.
"""
from __future__ import annotations

from typing import Callable, Optional

from harness.trace import CUSTOM_CALL

#: What the text of a tagged op holds, up to the stage's name.
TAG = 'robust_stage="'


def _xla_ms(ctx, pick: Callable) -> Optional[float]:
    """Device ms per step of the non-parent ops outside the Pallas kernels
    that ``pick`` selects by their text, per device; None when no op of
    the trace carries a stage tag."""
    ops = [o for o in ctx.trace.ops if not o.parent]
    if not any(TAG in o.text for o in ops):
        return None
    ns = sum(o.dur_ns for o in ops
             if CUSTOM_CALL not in o.text and pick(o.text))
    return 1e3 * ns * 1e-9 / ctx.trace.devices / ctx.steps


def stage_ms(ctx, stage: str) -> Optional[float]:
    """Device ms per step of the XLA ops tagged ``stage``."""
    tag = f'{TAG}{stage}"'
    return _xla_ms(ctx, lambda text: tag in text)


def unstaged_ms(ctx) -> Optional[float]:
    """Device ms per step of the XLA ops that carry no stage tag: copies,
    broadcasts and loop-carry moves the compiler makes on its own."""
    return _xla_ms(ctx, lambda text: TAG not in text)
