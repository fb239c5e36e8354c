"""mixtrim_roofline: the mix + trim kernel's share of its roofline: the
least time of one read of the (n, D) float32 stack and one write of the
direction (and 2 n^2 D operations with the NNM mix), over its measured
device time per step."""
from harness import costs


def read(ctx):
    s = ctx.trace.kernel_s(costs.KERNELS["mixtrim"])
    if s is None:
        return None
    n = int(ctx.cell.traffic["workers"])
    mix = ctx.cell.traffic["agg"].startswith("nnm+")
    least = costs.roofline_s(*costs.mixtrim_cost(n, ctx.leaf_widths, mix),
                             ctx.peak)
    return 100.0 * least / (s / ctx.steps)
