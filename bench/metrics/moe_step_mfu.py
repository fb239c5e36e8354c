"""moe_step_mfu: ``step_mfu``'s arithmetic in the expert-layer cell: model
FLOPs of all workers' forward and backward passes in the traced steps
(expert leaves at their expected uniform load), over the traced window x
chips x the chip's peak."""
from harness import costs


def read(ctx):
    flops = costs.model_flops_per_step(ctx.cell.reference,
                                       ctx.cell.config["sizes"],
                                       ctx.cell.traffic)
    return 100.0 * flops * ctx.steps / (
        ctx.trace.window_s * ctx.chips * ctx.peak["flops"])
