"""expert_ms: device milliseconds per step of the ops tagged
``moe_part="experts"``: the held experts' grouped SwiGLU products (custom
calls included), forward and backward."""
from harness import experts


def read(ctx):
    return experts.part_ms(ctx, "experts")
