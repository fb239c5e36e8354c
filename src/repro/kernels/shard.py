"""Distributed kernel backend: shard_map'd aggregation primitives.

The single-device Pallas pipeline streams ONE contiguous ``(n, D)`` worker
stack through the blocked gram / streamed combine / fused mixtrim kernels.
This module is its multi-device form (``backend="pallas_sharded"``): the
stack is sharded along the feature dim D over one mesh axis, and

* **gram** runs the blocked kernel per shard and ``psum``s the tiny
  ``(n, n)`` partial Gram matrices across the mesh — the only collective
  the whole pipeline needs, O(n^2) bytes;
* coefficient / NNM math happens replicated OUTSIDE the shard_map (it is
  O(n^2) and depends on the stack only through G);
* **combine** / **mixtrim** run shard-locally on the ``(n, D/k)`` block —
  per-column math, so the sharded result is the single-device result and
  the NNM-mixed stack never materializes in HBM on ANY device count.

Every function takes an explicit ``(mesh, axis)`` pair (resolved by
``repro.kernels.dispatch.resolve_shard_mesh``).  Routing and decision
recording stay in :mod:`repro.kernels.dispatch`; this module is pure
compute.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.bucketgram import bucket_means_gram as _bucketgram_op
from repro.kernels.bucketgram import pick_block_n as _pick_block_n
from repro.kernels.combine import combine as _combine_op
from repro.kernels.gram import gram as _gram_op
from repro.kernels.mixtrim import mixtrim as _mixtrim_op
from repro.kernels.mixtrim.ref import coordinate_rule
from repro.kernels import tiling
from repro.kernels.target import resolve_interpret

Array = jax.Array


def axis_size(mesh, axis: str) -> int:
    """Device count along one named axis of a concrete or abstract mesh."""
    return mesh.shape[axis]


def local_width(d: int, mesh, axis: str) -> int:
    """Columns of one shard of a d-wide stack split over ``axis`` (d is
    zero-padded to a multiple of the shard count)."""
    return -(-d // axis_size(mesh, axis))


def _resolve(mesh, axis, x, block_d, interpret):
    """Common per-call plumbing: column padding, local tile width,
    interpret."""
    n, d = x.shape
    pad = (-d) % axis_size(mesh, axis)
    bd = tiling.block_width(local_width(d, mesh, axis), block_d, n, x.dtype)
    return pad, bd, resolve_interpret(interpret)


def _pad_cols(x: Array, pad: int) -> Array:
    """Zero-pad the feature dim so it divides the shard count (exact: zero
    columns add nothing to the gram and combine/trim to a sliced-off 0)."""
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


def sharded_gram(x: Array, *, mesh: jax.sharding.Mesh, axis: str,
                 block_d: Optional[int] = None,
                 interpret: Optional[bool] = None) -> Array:
    """(n, D) -> replicated (n, n) fp32 Gram via per-shard kernels + psum."""
    pad, bd, interpret = _resolve(mesh, axis, x, block_d, interpret)

    def body(xl):
        g = _gram_op(xl, block_d=bd, use_pallas=True, interpret=interpret)
        return jax.lax.psum(g, axis)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(None, axis),),
                       out_specs=P(), check_vma=False)
    return fn(_pad_cols(x, pad))


def sharded_combine(x: Array, coeff: Array, *, mesh: jax.sharding.Mesh,
                    axis: str, block_d: Optional[int] = None,
                    interpret: Optional[bool] = None) -> Array:
    """(n, D), replicated (n,) -> (D,) sharded along ``axis``.

    Per-column math: each shard's slice of the output is exactly what the
    single-device combine kernel computes for those columns."""
    d = x.shape[1]
    pad, bd, interpret = _resolve(mesh, axis, x, block_d, interpret)

    def body(xl, cl):
        return _combine_op(xl, cl, block_d=bd, use_pallas=True,
                           interpret=interpret)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(None, axis), P()),
                       out_specs=P(axis), check_vma=False)
    return fn(_pad_cols(x, pad), coeff)[:d]


def _replicated_operands(x: Array, pad: int, axis: str, m: Optional[Array],
                         f) -> tuple[list, list]:
    """The shard_map operands of a coordinate rule and their specs: the
    D-sharded stack, then ``m`` and a traced ``f`` replicated where
    present (a python-int f stays in the body's closure)."""
    operands: list = [_pad_cols(x, pad)]
    in_specs: list = [P(None, axis)]
    if m is not None:
        operands.append(m)
        in_specs.append(P())
    if not isinstance(f, int):
        operands.append(jnp.asarray(f, jnp.int32))
        in_specs.append(P())
    return operands, in_specs


def sharded_mixtrim(x: Array, m: Optional[Array], f, *, mode: str,
                    mesh: jax.sharding.Mesh, axis: str,
                    block_d: Optional[int] = None,
                    interpret: Optional[bool] = None) -> Array:
    """(n, D) -> (D,): fused mix + trim/median, shard-local per d-block.

    ``m`` and a traced ``f`` ride into the shard_map as replicated
    operands; the padded sentinel bitonic sort inside the kernel handles
    any n.  The mixed stack only ever exists as (n, W) VMEM tiles on each
    device."""
    d = x.shape[1]
    pad, bd, interpret = _resolve(mesh, axis, x, block_d, interpret)
    has_m = m is not None
    traced = not isinstance(f, int)

    def body(xl, *rest):
        ml = rest[0] if has_m else None
        return _mixtrim_op(xl, ml, rest[-1] if traced else f, mode=mode,
                           block_d=bd, interpret=interpret)

    operands, in_specs = _replicated_operands(x, pad, axis, m, f)
    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=P(axis), check_vma=False)
    return fn(*operands)[:d]


def sharded_bucketgram(x: Array, bmat: Array, *, mesh: jax.sharding.Mesh,
                       worker_axis: Optional[str], model_axis: str,
                       with_gram: bool = True,
                       block_n: Optional[int] = None,
                       block_d: Optional[int] = None,
                       interpret: Optional[bool] = None
                       ) -> tuple[Array, Optional[Array]]:
    """Hierarchical reduction on a (possibly 2-D) mesh: (n, D) stack +
    (n_b, n) assignment -> (bucket means (n_b, D) sharded along
    ``model_axis``, replicated (n_b, n_b) fp32 reduced Gram | None).

    The stack lives sharded along BOTH mesh axes (worker shards x D
    shards); ``bmat``'s columns shard with the workers.  Each device runs
    the fused bucketgram kernel on its local (n/w, D/k) tile; the only
    collectives are REDUCED-population ones — a psum of (n_b, D/k) partial
    means across the worker shards (s-fold smaller than gathering the
    stack, and valid for ANY global permutation: bucket membership never
    needs to align with the shard boundaries) and a psum of the tiny
    (n_b, n_b) partial Grams across the D shards.  No (n, D)-shaped value
    crosses a device boundary and none materializes outside the VMEM
    tiles.

    ``worker_axis=None`` is the 1-D form: the stack shards only along D,
    ``bmat`` replicates, and the fused kernel emits means AND partial Gram
    in one pass per shard (single collective: the Gram psum).
    """
    n, d = x.shape
    kd = axis_size(mesh, model_axis)
    kw = axis_size(mesh, worker_axis) if worker_axis is not None else 1
    pad_d = (-d) % kd
    pad_n = (-n) % kw
    interpret = resolve_interpret(interpret)
    bn = block_n if block_n is not None else _pick_block_n((n + pad_n) // kw)
    xw = _pad_cols(x, pad_d)
    if pad_n:
        # Zero worker rows + zero assignment columns: phantom workers
        # belong to no bucket, so the padded reduction is exact.
        xw = jnp.pad(xw, ((0, pad_n), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad_n)))

    if worker_axis is None:
        def body1(xl, bl):
            y, g = _bucketgram_op(xl, bl, with_gram=with_gram, block_n=bn,
                                  block_d=block_d, interpret=interpret)
            if not with_gram:
                return (y,)
            return y, jax.lax.psum(g, model_axis)

        fn = jax.shard_map(body1, mesh=mesh,
                           in_specs=(P(None, model_axis), P()),
                           out_specs=((P(None, model_axis), P()) if with_gram
                                      else (P(None, model_axis),)),
                           check_vma=False)
        out = fn(xw, bmat)
        y = out[0][:, :d]
        return (y, out[1]) if with_gram else (y, None)

    def body2(xl, bl):
        # Per-device partial means over the local worker rows; the psum
        # over the worker shards completes every bucket regardless of how
        # the permutation scattered its members across devices.
        y_part, _ = _bucketgram_op(xl, bl, with_gram=False, block_n=bn,
                                   block_d=block_d, interpret=interpret)
        y = jax.lax.psum(y_part, worker_axis)
        if not with_gram:
            return (y,)
        g = _gram_op(y, block_d=block_d, use_pallas=True,
                     interpret=interpret)
        return y, jax.lax.psum(g, model_axis)

    fn = jax.shard_map(body2, mesh=mesh,
                       in_specs=(P(worker_axis, model_axis),
                                 P(None, worker_axis)),
                       out_specs=((P(None, model_axis), P()) if with_gram
                                  else (P(None, model_axis),)),
                       check_vma=False)
    out = fn(xw, bmat)
    y = out[0][:, :d]
    return (y, out[1]) if with_gram else (y, None)


def sharded_meamed(x: Array, m: Optional[Array], f, *,
                   mesh: jax.sharding.Mesh, axis: str) -> Array:
    """(n, D) -> (D,): mean-around-median, shard-local jnp form.

    meamed has no fused kernel (recorded as a fallback by the dispatcher),
    but it IS coordinate-wise, so the jnp form still runs shard-locally —
    the mixed stack and the sort stay (n, D/k) per device.  The body
    applies the xla backend's own :func:`coordinate_rule` to the local
    columns, so parity with the other backends can never drift."""
    d = x.shape[1]
    k = axis_size(mesh, axis)
    pad = (-d) % k
    has_m = m is not None
    traced = not isinstance(f, int)

    def body(xl, *rest):
        y = xl if not has_m else jnp.einsum(
            "mn,nd->md", rest[0].astype(xl.dtype), xl,
            preferred_element_type=jnp.float32)
        return coordinate_rule(y, "meamed", rest[-1] if traced else f)

    operands, in_specs = _replicated_operands(x, pad, axis, m, f)
    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=P(axis), check_vma=False)
    return fn(*operands)[:d]
