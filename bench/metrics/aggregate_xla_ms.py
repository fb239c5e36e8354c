"""aggregate_xla_ms: device milliseconds per step of the aggregation's
ops outside its Pallas kernels (``robust_stage="aggregate"``, not a TPU
custom call): the NNM matrix, the padding of M and the leaves' views.
The kernels themselves are ``gram_ms`` and ``mixtrim_ms``."""
from harness import stages


def read(ctx):
    return stages.stage_ms(ctx, "aggregate")
