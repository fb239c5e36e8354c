"""kappa_ms: device milliseconds per step of the train step's ops tagged
``robust_stage="kappa"`` (``repro.obs.stages``), outside the Pallas
kernels."""
from harness import stages


def read(ctx):
    return stages.stage_ms(ctx, "kappa")
