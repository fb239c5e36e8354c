"""mixtrim_ms: device milliseconds per step of the fused mix + trim kernel
(``repro.kernels.mixtrim``), summed over its per-leaf calls."""
from harness import costs


def read(ctx):
    s = ctx.trace.kernel_s(costs.KERNELS["mixtrim"])
    return None if s is None else 1e3 * s / ctx.steps
