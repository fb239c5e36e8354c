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

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import shard as shardlib
from repro.kernels import tiling
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
