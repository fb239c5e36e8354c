"""compile_s: host seconds the process spent in XLA compiles or loading
compiled programs from the persistent cache, from its start to the end of
the window: the program's ``jax.compile_s`` counter
(``repro.obs.runtime.watch_compiles``)."""


def read(ctx):
    from repro.obs import runtime

    return runtime.counters().get("jax.compile_s")
