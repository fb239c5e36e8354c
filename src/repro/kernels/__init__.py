"""Pallas TPU kernels for the robust-aggregation hot path.

Each kernel subpackage follows the kernel.py (pl.pallas_call + BlockSpec)
/ ops.py (jit'd wrapper) / ref.py (pure-jnp oracle) layout.  Kernels target
TPU VMEM/MXU tiling and are validated in interpret mode on CPU.

Production code enters through :mod:`repro.kernels.dispatch`: the backend
layer ``repro.core.robust`` routes through when
``AggregatorSpec.backend`` resolves to "pallas" (per-leaf (n, R, C) leaf
views in the leaves' own layout, blocked gram, streamed combine, fused
mix+trim — see docs/perf.md).  The
"xla" backend and the distributed (GSPMD) path use the jnp oracles so the
CPU dry-run lowers; off-TPU, "pallas" runs the kernel bodies in interpret
mode.
"""
from repro.kernels.bucketgram import bucket_means_gram, bucket_means_gram_ref
from repro.kernels.combine import combine, combine_ref
from repro.kernels.gram import gram, gram_batched, gram_batched_ref, gram_ref
from repro.kernels.mixtrim import mixtrim, mixtrim_ref
from repro.kernels import dispatch, shard

__all__ = [
    "bucket_means_gram", "bucket_means_gram_ref",
    "combine", "combine_ref",
    "dispatch",
    "gram", "gram_batched", "gram_batched_ref", "gram_ref",
    "mixtrim", "mixtrim_ref",
    "shard",
]
