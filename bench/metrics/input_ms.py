"""input_ms: host milliseconds per traced step spent taking the next batch
from the feed (``repro.data.pipeline.worker_batches``) and putting it on
the device: the benchmark's ``bench.input`` span."""


def read(ctx):
    spans = ctx.trace.span_s("bench.input")
    return 1e3 * sum(spans) / len(spans) if spans else None
