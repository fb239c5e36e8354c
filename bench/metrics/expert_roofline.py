"""expert_roofline: the held experts' products' share of their roofline:
the least time of 3 passes x 2 x 3 d ff FLOPs per expected (token, held
expert) pair and of one read of the held weights and the pairs' rows per
pass, over ``expert_ms``."""
from harness import costs, experts


def read(ctx):
    ms = experts.part_ms(ctx, "experts")
    if not ms:
        return None
    least = costs.roofline_s(*experts.expert_cost(ctx.cell.config["sizes"],
                                                  ctx.cell.traffic), ctx.peak)
    return 100.0 * least / (ms * 1e-3)
