"""Distributed integration: the robust train step on a REAL (subprocess)
multi-device mesh, verifying sharded == single-device numerics, plus
roofline HLO parsing units.

The 8-device run executes in a subprocess because jax locks the device
count at first init (conftest keeps the main process at 1 device).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.launch.roofline import collective_bytes, shape_bytes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_shape_bytes():
    assert shape_bytes("bf16[16,1024]{1,0}") == 16 * 1024 * 2
    assert shape_bytes("f32[8]") == 32
    assert shape_bytes("(f32[2,2], s32[3])") == 16 + 12
    assert shape_bytes("pred[7]") == 7


def test_collective_bytes_parser():
    hlo = textwrap.dedent("""
      %p0 = f32[128,64]{1,0} parameter(0)
      %ag = f32[2048,64]{1,0} all-gather(%p0), dimensions={0}
      %ar = f32[128,64]{1,0} all-reduce(%p0), to_apply=%sum
      ROOT %out = f32[128,64]{1,0} add(%ar, %ar)
    """)
    got = collective_bytes(hlo)
    assert got["all-gather"] == 128 * 64 * 4
    assert got["all-reduce"] == 128 * 64 * 4
    assert got["reduce-scatter"] == 0


_DIST_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(%(repo)r, "src"))
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import reduced_config
from repro.core.types import AggregatorSpec
from repro.models import build_model, mesh_axes_scope, partition_specs, abstract
from repro.models.common import MeshAxes
from repro.optim import sgd
from repro.optim.schedules import constant
from repro.launch.mesh import as_shardings, make_debug_mesh
from repro.training import ByzantineConfig, TrainerConfig, build_train_step, init_state

W, B, S = 4, 2, 16
mesh = make_debug_mesh(4, 2)
cfg = reduced_config("qwen2-7b")
axes = MeshAxes(data=("data",), model="model", model_par=2, shard_kv=True,
                workers_on_data=True)

def run(distributed):
    key = jax.random.PRNGKey(0)
    ctx = mesh_axes_scope(axes if distributed else None)
    with ctx:
        model = build_model(cfg)
        params = model.init(key)
        tcfg = TrainerConfig(algorithm="dshb",
                             agg=AggregatorSpec(rule="cwtm", f=1, pre="nnm"),
                             byz=ByzantineConfig(f=1, attack="alie"),
                             worker_axes=("data",) if distributed else None)
        optimizer = sgd(clip=1.0)
        step = build_train_step(model.loss, optimizer, tcfg, constant(1e-2))
        state = init_state(params, optimizer, W, tcfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (W, B, S), 0,
                                    cfg.vocab_size)
        batch = {"tokens": tokens, "labels": tokens}
        if distributed:
            pspecs = partition_specs(model.param_descs())
            state_specs = dict(
                params=pspecs, opt_state=(), step=P(),
                momentum=[P(("data",)) for _ in state["momentum"]])
            batch_specs = {k: P(("data",)) for k in batch}
            with jax.set_mesh(mesh):
                step_j = jax.jit(step, in_shardings=as_shardings(
                    (state_specs, batch_specs, P()), mesh))
                state2, metrics = step_j(state, batch, jax.random.PRNGKey(2))
                state2 = jax.device_get(state2)
        else:
            step_j = jax.jit(step)
            state2, metrics = step_j(state, batch, jax.random.PRNGKey(2))
    return state2, float(metrics["loss"])

s_dist, l_dist = run(True)
s_single, l_single = run(False)
max_err = 0.0
for a, b in zip(jax.tree_util.tree_leaves(s_dist["params"]),
                jax.tree_util.tree_leaves(s_single["params"])):
    max_err = max(max_err, float(np.abs(np.asarray(a, np.float32) -
                                        np.asarray(b, np.float32)).max()))
print(json.dumps({"loss_dist": l_dist, "loss_single": l_single,
                  "max_param_err": max_err}))
"""


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    script = _DIST_SCRIPT % {"repo": REPO}
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(res["loss_dist"] - res["loss_single"]) < 1e-3, res
    assert res["max_param_err"] < 5e-3, res
