"""Shared types for the Byzantine-robust aggregation core.

The canonical input of every aggregation primitive is a 2-D stack
``x : (n, d)`` holding one vector per worker.  Pytree-level wrappers live in
:mod:`repro.core.robust`.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax

Array = jax.Array
# An aggregation rule maps (n, d) -> (d,).
AggFn = Callable[..., Array]


@dataclasses.dataclass(frozen=True)
class AggregatorSpec:
    """Fully describes a robust aggregation pipeline.

    Attributes:
      rule: base rule name ("average", "krum", "multikrum", "gm", "autogm",
        "cwmed", "cwtm", "mda", "meamed").
      f: number of Byzantine workers tolerated (f < n/2).
      pre: optional pre-aggregation ("nnm", "bucketing", or None).
      bucket_size: Bucketing bucket size s (defaults to floor(n / 2f));
        shared by ``pre="bucketing"`` and the hierarchical stage.
      hier: hierarchical aggregation — reduce the n-worker stack to
        ceil(n/s) random bucket means (Karimireddy et al. bucketing as a
        PRE-reduction) before ``pre``/``rule`` run on the reduced
        population with the f' = f adjustment, turning the O(n^2) stages
        into O((n/s)^2).  Composes with ``pre="nnm"`` (bucketing -> NNM ->
        rule); mutually exclusive with ``pre="bucketing"`` (that IS a
        bucketing stage) and ``sketch_dim``.  s=1 is an exact no-op
        (singleton buckets; the permutation is skipped so the pipeline is
        bitwise the dense one).  Requires a PRNG key; a traced f needs
        an explicit ``bucket_size``.  Static bucket-key material for the
        fleet engine — the per-lane permutation key stays a traced
        operand.
      gm_iters: Weiszfeld iteration count for GM (and AutoGM's inner solve).
      gm_eps: Weiszfeld smoothing epsilon.
      autogm_lamb: AutoGM weight-regularization strength, in units of the
        mean distance to the uniform-weight GM (scale-free; large values
        recover plain GM, small values concentrate weight on inliers).
      autogm_iters: AutoGM outer alternating iterations (each runs one
        simplex-projected weight update plus a gm_iters Weiszfeld solve).
      backend: kernel backend for the aggregation hot path.  "xla" is the
        leaf-streamed jnp pipeline (GSPMD-friendly); "pallas" flattens the
        worker stack to one (n, D) buffer and runs the blocked gram /
        streamed combine / fused mix+trim kernels (interpret mode off-TPU);
        "pallas_sharded" shard_maps that pipeline along D over a mesh axis
        (per-shard gram + psum'd (n, n) partials, shard-local
        combine/mixtrim — degrades to "xla", RECORDED, without a
        multi-device mesh); "pallas_hier" implies ``hier`` and runs the
        hierarchical reduction on a (possibly 2-D workers x model) mesh —
        the stack lives sharded along n AND D, the fused bucketed-gram
        kernel reduces it per device, and only tiny reduced collectives
        cross shards (degrades to the dense bucketing path, RECORDED,
        without a multi-device mesh); "auto" picks "pallas" on a
        single-device TPU, "pallas_sharded" on a multi-device TPU
        ("pallas_hier" instead when ``hier`` is set), and "xla" elsewhere.
        Routing decisions — oracle fallbacks, the mesh/device-count
        resolution — are queryable via
        ``repro.kernels.dispatch.last_dispatch()``.
    """

    rule: str = "cwtm"
    f: int = 0
    pre: Optional[str] = "nnm"
    bucket_size: Optional[int] = None
    hier: bool = False
    gm_iters: int = 8
    gm_eps: float = 1e-8
    autogm_lamb: float = 1.0
    autogm_iters: int = 4
    backend: str = "auto"
    # --- beyond-paper performance options (EXPERIMENTS.md §Perf) ---
    # Transport dtype for the worker-axis all-gathers.  Distance ranks and
    # all gram/coefficient math stay fp32; bf16 transport halves the
    # dominant collective bytes at the cost of ~3 mantissa digits on the
    # gathered values themselves.
    transport_dtype: Optional[str] = None          # None (=fp32) | "bf16"
    # Johnson-Lindenstrauss sketch for neighbor selection: the Gram pass
    # runs on a (n, sketch_dim) random projection computed worker-locally,
    # removing one of the two full-stack all-gather passes for the
    # coordinate-wise rules.  0 disables (paper-faithful exact distances).
    sketch_dim: int = 0

    def describe(self) -> str:
        pre = f"{self.pre}+" if self.pre else ""
        return f"{pre}{self.rule}(f={self.f})"


#: Rules whose output is a linear combination coeff @ x with coeff a pure
#: function of the Gram matrix.  For these the distributed pipeline never
#: materializes the mixed stack (see DESIGN.md §3).
GRAM_RULES = frozenset({"average", "krum", "multikrum", "gm", "autogm",
                        "mda"})

#: Rules that operate coordinate-wise on the (optionally mixed) stack.
COORDINATE_RULES = frozenset({"cwmed", "cwtm", "meamed"})

ALL_RULES = tuple(sorted(GRAM_RULES | COORDINATE_RULES))

ATTACKS = ("none", "alie", "foe", "sf", "lf", "mimic", "alie_opt", "foe_opt",
           "nan", "inf")
