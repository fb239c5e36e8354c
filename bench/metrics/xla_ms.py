"""xla_ms: device milliseconds per step outside the Pallas kernels: the
per-worker forward and backward, momentum, attack, NNM matrix, kappa-hat
and optimizer (busy time minus every Pallas kernel's time)."""


def read(ctx):
    kernels = ctx.trace.custom_calls_s() or 0.0
    return 1e3 * (ctx.trace.busy_s - kernels) / ctx.steps
