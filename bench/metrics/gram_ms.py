"""gram_ms: device milliseconds per step of the blocked Gram kernel
(``repro.kernels.gram``), summed over its per-leaf calls."""
from harness import costs


def read(ctx):
    s = ctx.trace.kernel_s(costs.KERNELS["gram"])
    return None if s is None else 1e3 * s / ctx.steps
