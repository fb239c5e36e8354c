"""Parameter descriptors, sharding context, and shared layer math.

Single-source-of-truth parameter system: every model builds a pytree of
:class:`ParamDesc` (shape + dtype + logical axes + init recipe).  The same
tree serves three consumers:

* ``materialize``          -> real initialized params (smoke tests, training)
* ``abstract``             -> ShapeDtypeStructs (dry-run lowering, no alloc)
* ``partition_specs``      -> PartitionSpecs via the logical->mesh axis map

Logical axis names used throughout the zoo:
  "embed"   d_model            (replicated; activations shard on batch)
  "heads"   attention heads    -> "model" when shardable
  "kv"      kv heads           -> "model" only when divisible
  "ff"      mlp hidden         -> "model"
  "vocab"   vocabulary         -> "model"
  "expert"  MoE experts        -> "model" when E % par == 0 else replicated
  "layers"  scan axis          (never sharded)
  "batch"   global batch       -> worker/data axes (activations & caches)
  "seq"     sequence           -> data axes for long-context decode caches
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

Array = jax.Array


# ---------------------------------------------------------------------------
# Sharding context.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Maps logical axes to physical mesh axis names."""
    data: tuple[str, ...] = ("data",)      # worker/data-parallel axes
    model: str = "model"
    model_par: int = 1                      # size of the model axis
    shard_kv: bool = True                   # kv heads divisible by model_par
    shard_expert: bool = True               # experts divisible by model_par
    expert_fsdp: bool = False               # ZeRO-3 experts over data axes
    seq_par: bool = False                   # sequence-parallel residual stream
    # True while tracing inside vmap(spmd_axis_name=data): activation specs
    # must not mention the worker axes (JAX forbids it); the vmap itself
    # shards the worker dim.  Weight specs (applied via jit in_shardings)
    # still use the data axes.
    workers_on_data: bool = False
    # Pad kv heads to the mesh so KV caches shard over the model axis
    # (EXPERIMENTS.md §Perf / minitron decode hillclimb).
    pad_kv_to_mesh: bool = False

    def logical_to_spec(self, axes: tuple[Optional[str], ...]) -> P:
        parts = []
        for ax in axes:
            if ax in ("heads", "ff", "vocab"):
                parts.append(self.model)
            elif ax == "kv":
                parts.append(self.model if self.shard_kv else None)
            elif ax == "expert":
                parts.append(self.model if self.shard_expert else None)
            elif ax == "ff_inner":
                # Expert-internal ff dim: shards over model when the expert
                # dim cannot; under FSDP-with-sharded-experts it takes the
                # data axes instead.
                if self.shard_expert:
                    parts.append(self.data if self.expert_fsdp else None)
                else:
                    parts.append(self.model)
            elif ax == "expert_embed":
                # Expert d_model dim: the FSDP axis when experts replicate.
                if self.expert_fsdp and not self.shard_expert:
                    parts.append(self.data)
                else:
                    parts.append(None)
            elif ax == "ff_act":
                # MoE activation ff dim: follows the model axis only when
                # the expert dim does not occupy it.
                parts.append(None if self.shard_expert else self.model)
            elif ax == "batch":
                parts.append(None if self.workers_on_data else self.data)
            elif ax == "seq_shard":
                parts.append(None if self.workers_on_data else self.data)
            elif ax == "seq_model":
                parts.append(self.model)
            elif ax == "seq_both":
                parts.append(self.model if self.workers_on_data
                             else tuple(self.data) + (self.model,))
            else:
                parts.append(None)
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)


_CTX: list[Optional[MeshAxes]] = [None]


def set_mesh_axes(axes: Optional[MeshAxes]) -> None:
    _CTX[0] = axes


def get_mesh_axes() -> Optional[MeshAxes]:
    return _CTX[0]


class mesh_axes_scope:
    def __init__(self, axes: Optional[MeshAxes]):
        self.axes = axes

    def __enter__(self):
        self.prev = _CTX[0]
        _CTX[0] = self.axes
        return self.axes

    def __exit__(self, *exc):
        _CTX[0] = self.prev
        return False


def constrain(x: Array, *logical: Optional[str]) -> Array:
    """Apply a sharding constraint from logical axis names (no-op w/o ctx).

    Under ``vmap(..., spmd_axis_name=...)`` the worker axis is prepended by
    JAX automatically, so specs here describe the per-worker logical shape.
    """
    ctx = get_mesh_axes()
    if ctx is None:
        return x
    spec = ctx.logical_to_spec(tuple(logical))
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Parameter descriptors.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamDesc:
    shape: tuple[int, ...]
    dtype: Any = jnp.float32
    axes: tuple[Optional[str], ...] = ()
    init: str = "normal"          # normal | zeros | ones | embed
    scale: float = 1.0            # stddev multiplier (normal) / value

    def __post_init__(self):
        assert len(self.axes) in (0, len(self.shape)), (self.shape, self.axes)


def _is_desc(x) -> bool:
    return isinstance(x, ParamDesc)


def abstract(tree) -> Any:
    """ParamDesc tree -> ShapeDtypeStruct tree (no device allocation).

    Sharding is communicated separately through ``partition_specs`` +
    ``jit(in_shardings=...)`` so the same abstract tree serves every mesh.
    """
    def go(d: ParamDesc):
        return jax.ShapeDtypeStruct(d.shape, d.dtype)
    return jax.tree_util.tree_map(go, tree, is_leaf=_is_desc)


def partition_specs(tree) -> Any:
    """ParamDesc tree -> PartitionSpec tree via the active context."""
    ctx = get_mesh_axes()
    assert ctx is not None, "partition_specs requires a mesh-axes scope"

    def go(d: ParamDesc):
        return ctx.logical_to_spec(d.axes) if d.axes else P()
    return jax.tree_util.tree_map(go, tree, is_leaf=_is_desc)


def materialize(tree, key: Array) -> Any:
    """Initialize a ParamDesc tree (deterministic per-leaf-path keys)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_desc)

    def init_one(path, d: ParamDesc):
        label = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, zlib_hash(label))
        if d.init == "zeros":
            return jnp.zeros(d.shape, d.dtype)
        if d.init == "ones":
            return jnp.full(d.shape, d.scale or 1.0, d.dtype)
        if d.init in ("normal", "embed"):
            fan_in = d.shape[-2] if len(d.shape) >= 2 and d.init == "normal" else d.shape[-1]
            std = d.scale / math.sqrt(max(1, fan_in))
            return (std * jax.random.normal(k, d.shape)).astype(d.dtype)
        raise ValueError(d.init)

    leaves = [init_one(p, d) for p, d in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def zlib_hash(s: str) -> int:
    import zlib
    return zlib.crc32(s.encode()) & 0x7FFFFFFF


# ---------------------------------------------------------------------------
# Layer math.
# ---------------------------------------------------------------------------

def rms_norm(x: Array, gamma: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: Array, gamma: Array, beta: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float = 1e4) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


#: YaRN's correction range, in rotations over the original context
#: (transformers' defaults, and Mellum2's published values).
YARN_BETA_FAST, YARN_BETA_SLOW = 32.0, 1.0


def yarn_freqs(head_dim: int, theta: float, factor: float,
               original: int) -> Array:
    """YaRN's rotary frequencies (transformers' ``_compute_yarn_parameters``):
    interpolated by ``factor`` below the correction range, extrapolated
    above it, and a linear ramp between, over the ``original`` context."""
    def corr_dim(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr_dim(YARN_BETA_FAST)), 0)
    high = min(math.ceil(corr_dim(YARN_BETA_SLOW)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extra = 1 - ramp                       # share kept unscaled
    pos = theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
    freqs = (1 / (factor * pos)) * (1 - extra) + (1 / pos) * extra
    return jnp.asarray(freqs, jnp.float32)


def rope_table(cfg, kind: Optional[str]) -> tuple[Optional[Array], float]:
    """(frequencies, cos/sin scale) of a layer of type ``kind``: YaRN's
    table and attention factor 0.1 ln(factor) + 1 on "full" layers of a
    config that sets ``yarn_factor``, else (None, 1.0), the plain table of
    ``rope_theta``."""
    if kind != "full" or not cfg.yarn_factor:
        return None, 1.0
    freqs = yarn_freqs(cfg.head_dim, cfg.rope_theta, cfg.yarn_factor,
                       cfg.yarn_original_max_position)
    return freqs, 0.1 * math.log(cfg.yarn_factor) + 1


def apply_rope(x: Array, positions: Array, theta: float = 1e4, *,
               freqs: Optional[Array] = None, scale: float = 1.0) -> Array:
    """Rotary embedding.  x: (..., seq, heads, head_dim); positions: (..., seq).
    ``freqs`` replaces the table of ``theta``; ``scale`` multiplies cos
    and sin (YaRN's attention factor)."""
    head_dim = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(head_dim, theta)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, dim: int) -> Array:
    pos = np.arange(seq)[:, None]
    i = np.arange(dim // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / dim)
    emb = np.concatenate([np.sin(angle), np.cos(angle)], axis=-1)
    return jnp.asarray(emb, dtype=jnp.float32)


def pad_heads(hq: int, hkv: int, par: int, *, pad_kv: bool = False
              ) -> tuple[int, int, bool, bool]:
    """MaxText-style mesh padding for attention heads.

    Returns (hq_padded, hkv_padded, shard_q, shard_kv).  Policy (DESIGN.md):
    models with hq < par replicate attention (small models); otherwise hq is
    padded to a multiple of par, bumping hkv to a divisor of hq_padded if
    the group structure breaks; kv shards only when hkv_padded % par == 0.

    ``pad_kv=True`` additionally pads the kv-head count up to the mesh so
    the KV cache can shard over the model axis (the §Perf fix for the
    replicated-kv decode scatter; trades 2x kv param/cache padding for
    shard-local cache updates).
    """
    if par <= 1 or hq < par:
        return hq, hkv, False, False
    hq_p = -(-hq // par) * par
    hkv_p = hkv
    if hq_p % hkv_p != 0:
        cands = [h for h in range(hkv, hq_p + 1) if hq_p % h == 0]
        hkv_p = cands[0]
    if pad_kv and hkv_p % par != 0:
        hkv_p = par             # par divides hq_p, so grouping stays exact
    return hq_p, hkv_p, True, hkv_p % par == 0
