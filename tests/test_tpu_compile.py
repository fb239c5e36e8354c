"""The aggregation kernels compile for a TPU v5e (no chip needed).

Each case lowers a kernel of the hot path with ``interpret=False`` for a
described ``v5e:2x2`` topology at a width of millions of coordinates,
through the grid tile the kernels pick for that shape when given no
``block_d`` (``tiling.pick_block_d``: tens of thousands of lanes at n=4),
so Mosaic refuses here what it would refuse on the chip (unsupported
primitives, unaligned slices, too much VMEM), and checks that the compiled
program holds the kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module fixture: only the worker that
runs these tests loads the TPU compiler, and where it cannot be described
the tests skip.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import robust as robust_lib
from repro.core.attacks import apply_attack_tree
from repro.core.robust import AggregatorSpec
from repro.kernels import shard as shardlib
from repro.kernels import target, tiling
from repro.kernels.bucketgram import bucket_means_gram
from repro.kernels.combine import combine
from repro.kernels.gram import gram, gram_batched
from repro.kernels.mixtrim import mixtrim, mixtrim_dyn

#: Stack width: millions of coordinates, not a multiple of the tile width
#: (the ragged last tile is part of what must compile).
D = 3 * 2**20 + 200


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes) -> str:
    """HLO text of ``fn`` compiled for the described chip(s)."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _mixtrim_all(x, m, f):
    """Every static-f form: trim and median, with and without the mix."""
    return [mixtrim(x, mm, f=f, mode=mode, interpret=False)
            for mode in ("trim", "med") for mm in (m, None)]


def _mixtrim_dyn_all(x, m, f):
    return [mixtrim_dyn(x, mm, f, interpret=False) for mm in (m, None)]


KERNELS = {
    "gram": lambda x, m, f: gram(x, interpret=False),
    "gram_batched": lambda x, m, f: gram_batched(
        jnp.stack([x, 2 * x]), interpret=False),
    "combine": lambda x, m, f: combine(x, m[0], interpret=False),
    "mixtrim": lambda x, m, f: _mixtrim_all(x, m, int(f)),
    "mixtrim_dyn": _mixtrim_dyn_all,
}


@pytest.mark.parametrize("n", [4, 17, 256])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, n):
    # the picked tile, several chunks wide, is what compiles below
    assert tiling.pick_block_d(n, D) > tiling.CHUNK
    f = (n - 1) // 4
    x = jax.ShapeDtypeStruct((n, D), jnp.float32, sharding=one_chip)
    m = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
    body = KERNELS[kernel]
    if kernel == "mixtrim":
        hlo = _compile(lambda x, m: body(x, m, f), x, m)
    else:
        fd = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        hlo = _compile(body, x, m, fd)
    assert "tpu_custom_call" in hlo


#: Leaf views at SmolLM-360M's widths (n=4): the MLP up leaf, a k/v leaf
#: held 960-minor, and the tall embedding held 49152-minor, whose row tile
#: overflows VMEM and splits into lane blocks; and a leaf at n=17.
LEAF_VIEWS = [(4, 30720, 2560), (4, 10240, 960), (4, 960, 49152),
              (17, 1000, 320)]


@pytest.mark.parametrize("shape", LEAF_VIEWS)
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_leaf_view_kernel_compiles_for_v5e(one_chip, kernel, shape):
    """Every kernel compiles on a (n, R, C) leaf view (the lane-batched
    Gram takes the flat view) through the block it picks, and reads its
    stack operand at rank 3."""
    n = shape[0]
    f = (n - 1) // 4
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    m = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
    body = KERNELS[kernel]
    if kernel == "gram_batched":
        x = jax.ShapeDtypeStruct((n, shape[1] * shape[2]), jnp.float32,
                                 sharding=one_chip)
    if kernel == "mixtrim":
        hlo = _compile(lambda x, m: body(x, m, f), x, m)
    else:
        fd = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        hlo = _compile(body, x, m, fd)
    assert "tpu_custom_call" in hlo
    if kernel != "gram_batched":
        assert f"f32[{n},{shape[1]},{shape[2]}]{{2,1,0}}" in hlo


def test_leaf_view_mixtrim_dyn_vmap_compiles_for_v5e(one_chip):
    """The fleet's lane vmap of the dynamic-f kernel over leaf views."""
    xs = jax.ShapeDtypeStruct((3, 4, 1024, 960), jnp.float32,
                              sharding=one_chip)
    m = jax.ShapeDtypeStruct((3, 4, 4), jnp.float32, sharding=one_chip)
    fs = jax.ShapeDtypeStruct((3,), jnp.int32, sharding=one_chip)
    hlo = _compile(jax.vmap(lambda x, m, f: mixtrim_dyn(x, m, f,
                                                        interpret=False)),
                   xs, m, fs)
    assert "tpu_custom_call" in hlo


def test_bucketgram_compiles_for_v5e(one_chip):
    n, n_b = 1024, 128
    x = jax.ShapeDtypeStruct((n, 2**20), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((n_b, n), jnp.float32, sharding=one_chip)
    hlo = _compile(functools.partial(bucket_means_gram, interpret=False),
                   x, b)
    assert "tpu_custom_call" in hlo


def test_sharded_gram_mixtrim_compiles_for_v5e_2x2(topo):
    """The multi-chip form: gram and NNM+CWTM shard_map'd along D over the
    four described chips (n=17, f=4: the paper's setting)."""
    mesh = jax.sharding.Mesh(np.asarray(topo.devices), ("shard",))
    n, f = 17, 4
    x = jax.ShapeDtypeStruct((n, 4 * D), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, "shard")))
    m = jax.ShapeDtypeStruct((n, n), jnp.float32,
                             sharding=NamedSharding(mesh, P()))

    def step(x, m):
        g = shardlib.sharded_gram(x, mesh=mesh, axis="shard",
                                  interpret=False)
        out = shardlib.sharded_mixtrim(x, m, f, mode="trim", mesh=mesh,
                                       axis="shard", interpret=False)
        return g, out

    hlo = _compile(step, x, m)
    assert "tpu_custom_call" in hlo
    assert "all-reduce" in hlo      # the psum of the per-shard Grams


#: A SmolLM-shaped worker stack at a reduced size (n=4, 4 layers, d=192,
#: ff=320): its ranks and minor dims as the momentum holds them, a (d, ff)
#: and an (ff, d) leaf with ff not a multiple of 128, a tall embedding, a
#: per-layer norm and a 2-D leaf.
STACK = {"up": (4, 4, 192, 320), "down": (4, 4, 320, 192),
         "embed": (4, 4096, 192), "norm": (4, 4, 192), "final": (4, 192)}

_ARRAY = re.compile(r"\b(?:f32|bf16)\[([\d,]*)\]")
#: An fp32 value's definition: its name and layout.
_DEF = re.compile(r"%([\w.\-]+) = f32\[[\d,]*\]\{([^}]*)\}")
#: A copy or a physical reshape (a layout-preserving one is a bitcast by
#: now) of an fp32 value: name, dims, layout, op, operand.
_LAYOUT_OP = re.compile(r"%([\w.\-]+) = f32\[([\d,]*)\]\{([^}]*)\} "
                        r"(copy|reshape)\(%([\w.\-]+)\)")


def _tiles(layout: str) -> str:
    """A layout less its memory space (``S(1)``)."""
    return re.sub(r"S\(\d+\)", "", layout)


def _elements(dims: str) -> int:
    return int(np.prod([int(v) for v in dims.split(",") if v]))


@pytest.mark.parametrize("pre", ["nnm", None])
def test_robust_step_reads_leaves_in_their_layout(one_chip, monkeypatch,
                                                  pre):
    """D-SHB momentum, the ALIE attack and single-device pallas
    aggregation, compiled for the chip: the kernels read every leaf as its
    (n, R, C) leaf view, in the layout the momentum update writes, so the
    program holds no compiler-made row loop (``wide.body``) and no copy
    or physical reshape as large as one leaf's honest rows, and every
    stack operand of a kernel is rank 3."""
    monkeypatch.setattr(target, "on_tpu", lambda: True)
    n, f = 4, 1
    spec = AggregatorSpec(rule="cwtm", f=f, pre=pre, backend="pallas")

    def step(params, momentum, grads):
        stack = jax.tree_util.tree_map(lambda m, g: 0.9 * m + 0.1 * g,
                                       momentum, grads)
        attacked = apply_attack_tree("alie", stack, f)
        direction = robust_lib.robust_aggregate(attacked, spec)
        new_params = jax.tree_util.tree_map(lambda p, d: p - 0.05 * d,
                                            params, direction)
        return new_params, stack

    def shapes(worker_axis):
        return {k: jax.ShapeDtypeStruct(v if worker_axis else v[1:],
                                        jnp.float32, sharding=one_chip)
                for k, v in STACK.items()}

    hlo = _compile(step, shapes(False), shapes(True), shapes(True))
    assert "tpu_custom_call" in hlo
    assert not re.search(r"^%wide\.body", hlo, re.M), "row loops"
    honest = (n - f) * int(np.prod(STACK["up"][1:])) * 4   # fp32 bytes
    defs = dict(_DEF.findall(hlo))
    for name, dims, layout, op, arg in _LAYOUT_OP.findall(hlo):
        if _elements(dims) * 4 < honest:
            continue
        # a copy that keeps its operand's layout only moves memory spaces
        moved = op == "copy" and _tiles(defs.get(arg, "")) == _tiles(layout)
        assert moved, f"{op} {name} f32[{dims}]{{{layout}}} of {arg}"
    kernels = re.findall(r'custom_call_target="tpu_custom_call", '
                         r"operand_layout_constraints="
                         r"\{((?:[^{}]|\{[^{}]*\})*)\}", hlo)
    assert kernels
    for operands in kernels:
        stack_operands = [dims for dims in _ARRAY.findall(operands)
                          if _elements(dims) > n * n]
        assert stack_operands, operands
        for dims in stack_operands:
            assert len(dims.split(",")) == 3, operands
