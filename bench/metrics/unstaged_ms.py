"""unstaged_ms: device milliseconds per step of the ops outside the Pallas
kernels that carry no stage tag (``repro.obs.stages``): copies,
broadcasts and loop-carry moves the compiler makes.  The check on the
tagging: with the six stage metrics it adds up to ``xla_ms``."""
from harness import stages


def read(ctx):
    return stages.unstaged_ms(ctx)
