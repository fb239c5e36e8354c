"""step_mfu: model FLOPs of all workers' forward and backward passes in the
traced steps (from the configuration's shapes, nothing recomputed) over
the traced window x chips x the chip's peak."""
from harness import costs


def read(ctx):
    flops = costs.model_flops_per_step(ctx.cell.reference,
                                       ctx.cell.config["sizes"],
                                       ctx.cell.traffic)
    return 100.0 * flops * ctx.steps / (
        ctx.trace.window_s * ctx.chips * ctx.peak["flops"])
