"""lower_s: host seconds the process spent tracing its jitted functions
and lowering them to MLIR, from its start to the end of the window: the
program's ``jax.lower_s`` counter (``repro.obs.runtime.watch_compiles``)."""


def read(ctx):
    from repro.obs import runtime

    return runtime.counters().get("jax.lower_s")
