"""route_ms: device milliseconds per step of the ops tagged
``moe_part="route"`` (``repro.models.moe``): router product, top-k,
dispatch sort, gathers, scatters and the combine, forward and backward."""
from harness import experts


def read(ctx):
    return experts.part_ms(ctx, "route")
