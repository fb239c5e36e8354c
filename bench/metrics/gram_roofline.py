"""gram_roofline: the Gram kernel's share of its roofline: the least time
of 2 n^2 D operations and one read of the (n, D) float32 stack plus the
per-leaf n x n outputs, over its measured device time per step."""
from harness import costs


def read(ctx):
    s = ctx.trace.kernel_s(costs.KERNELS["gram"])
    if s is None:
        return None
    n = int(ctx.cell.traffic["workers"])
    least = costs.roofline_s(*costs.gram_cost(n, ctx.leaf_widths), ctx.peak)
    return 100.0 * least / (s / ctx.steps)
