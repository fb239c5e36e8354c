"""Plain float32 layer math for the configurations' references.

Straightforward ``jax.numpy``; every contraction goes through a matmul
function ``mm(spec, a, b)`` so that one reference runs at two precisions:
``"fp32"`` (every product at ``Precision.HIGHEST``) and ``"fp8"`` (both
operands rounded to float8 e4m3 with one scale per tensor, gradients
passed straight through): the control, one precision below the bfloat16
that the configurations state.

Rounding goes through ``lax.reduce_precision`` or a narrower output
dtype, never a round trip of converts, which XLA may elide where it
allows excess precision.
"""
from __future__ import annotations

import zlib
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
MatMul = Callable[[str, Array, Array], Array]
HIGHEST = jax.lax.Precision.HIGHEST
#: Largest finite value of a float8 with 4 exponent and 3 mantissa bits
#: under IEEE rules, as ``lax.reduce_precision`` rounds.
E4M3_MAX = 240.0


def _fp8(x: Array) -> Array:
    """x rounded to float8 e4m3 under a per-tensor scale; the gradient
    passes straight through."""
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX)
    q = jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                 mantissa_bits=3) * scale
    return x + jax.lax.stop_gradient(q - x)


def matmul(mode: str) -> MatMul:
    if mode == "fp32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if mode == "fp8":
        return lambda spec, a, b: jnp.einsum(spec, _fp8(a), _fp8(b),
                                             precision=HIGHEST)
    raise ValueError(f"unknown precision {mode!r}")


def pad_vocab(vocab: int) -> int:
    """The vocabulary padded to a multiple of 128, as the head is held."""
    return -(-vocab // 128) * 128


def leaf_key(key: Array, path: str) -> Array:
    """The per-leaf key of the weight recipe: the seed's key folded with
    the CRC-32 of the leaf's path."""
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def init_leaf(key: Array, path: str, shape: tuple, kind: str) -> Array:
    """One weight as the recipe makes it, in bfloat16: "normal"
    N(0, 1/fan_in) with fan_in the second-to-last dim, "embed" N(0, 1/d)
    with d the last dim, "ones", "zeros"."""
    if kind == "ones":
        w = jnp.ones(shape, jnp.float32)
    elif kind == "zeros":
        w = jnp.zeros(shape, jnp.float32)
    else:
        fan_in = shape[-2] if (kind == "normal" and len(shape) >= 2) \
            else shape[-1]
        w = jax.random.normal(leaf_key(key, path), shape) / np.sqrt(fan_in)
    return w.astype(jnp.bfloat16)


def rms_norm(x: Array, g: Array, eps: float) -> Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def layer_norm(x: Array, g: Array, b: Array, eps: float) -> Array:
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def rope(x: Array, theta: float) -> Array:
    """Rotary embedding over (B, S, H, hd), halves rotated."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sinusoid(seq: int, dim: int) -> Array:
    pos = np.arange(seq)[:, None]
    i = np.arange(dim // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * i / dim)
    return jnp.asarray(np.concatenate([np.sin(ang), np.cos(ang)], -1),
                       jnp.float32)


def attention(mm: MatMul, q: Array, k: Array, v: Array, causal: bool
              ) -> Array:
    """Softmax attention.  q (B, S, Hq, hd); k, v (B, T, Hkv, hd), kv
    head h // (Hq / Hkv) serving query head h.  Returns (B, S, Hq * hd)."""
    b, s, hq, hd = q.shape
    g = hq // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    logits = mm("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    if causal:
        t = k.shape[1]
        mask = jnp.arange(s)[:, None] >= jnp.arange(t)[None, :]
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return mm("bhqk,bkhd->bqhd", probs, v).reshape(b, s, hq * hd)


def cross_entropy(logits: Array, labels: Array) -> Array:
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], -1).mean()
