"""sample_ms: host milliseconds per batch the program's input pipeline
spends sampling and stacking the workers' rows: the mean of the
``data.batch`` spans (``repro.data.pipeline.worker_batches``) in the
process's event ring.  Part of ``input_ms``, which also holds the
benchmark's own slicing and the copy to the device."""


def read(ctx):
    from repro.obs import runtime

    spans = runtime.history(name="data.batch", kind="span")
    return 1e3 * sum(s["dur"] for s in spans) / len(spans) if spans else None
