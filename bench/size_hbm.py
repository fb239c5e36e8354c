"""Compile a cell's train step for a described TPU v5e, without a chip,
and print the device bytes it needs: the prediction of ``step_hbm_gb``
and the largest per-worker batch that fits.

  JAX_PLATFORMS=cpu python3 bench/size_hbm.py --workload <cell> [--batch 2,3,4]

For each batch size (the traffic mix's own by default) it prints the
compiled step's arguments, outputs, temporaries and aliased bytes, their
total as ``step_hbm_gb`` counts it, and whether the total fits the
15.75 GiB that XLA may use on one v5e.  A compile is a fit and a program,
never a time.
"""
import argparse
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: Bytes XLA may use on one TPU v5e (16 GB of HBM).
V5E_USABLE = 15.75 * 2**30


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", default=None,
                    help="comma-separated per-worker batch sizes")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import program
    from harness.cells import find_cell
    from run import hbm_bytes

    # The kernels pick their compiled (not interpreted) form where the
    # default backend is a TPU: steer it for this compile.
    jax.default_backend = lambda: "tpu"
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    cell = find_cell(args.workload)
    sizes = cell.config["sizes"]
    batches = ([int(b) for b in args.batch.split(",")] if args.batch
               else [int(cell.traffic["batch"])])
    for b in batches:
        traffic = dict(cell.traffic, batch=b)
        prog = program.build(cell.config, traffic, iter(()), seed=0)
        key = jax.ShapeDtypeStruct((2,), np.uint32, sharding=chip)

        def place(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)
        state = jax.tree_util.tree_map(
            place, jax.eval_shape(prog.init, jax.random.PRNGKey(0)))
        n, seq = int(traffic["workers"]), int(traffic["seq"])
        batch = {"tokens": place(jax.ShapeDtypeStruct((n, b, seq), np.int32)),
                 "labels": place(jax.ShapeDtypeStruct((n, b, seq), np.int32))}
        if traffic.get("frames"):
            batch["frames"] = place(jax.ShapeDtypeStruct(
                (n, b, int(traffic["frames"]), sizes["d_model"]), np.float32))
        compiled = prog.step.lower(state, batch, key).compile()
        m = compiled.memory_analysis()
        total = hbm_bytes(compiled)
        print(f"{args.workload} batch={b}: arguments "
              f"{m.argument_size_in_bytes} outputs {m.output_size_in_bytes} "
              f"temporaries {m.temp_size_in_bytes} aliased "
              f"{m.alias_size_in_bytes} -> step_hbm_gb {total / 1e9!r} "
              f"({'fits' if total <= V5E_USABLE else 'does not fit'} "
              f"{V5E_USABLE / 1e9:.3f} GB)", flush=True)


if __name__ == "__main__":
    main()
