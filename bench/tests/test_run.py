"""A tiny cell end to end on the CPU (kernels interpreted), the control
and planted faults under the timed path, and the refusals: no chip, or
no program beside the benchmark."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import run as bench_run
import tiny
from harness import compare, program, reference
from harness.cells import ROOT, find_cell
from harness.traffic import worker_feed

SEED = 2**31 + 12345


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


def _run(tiny_root, **faults):
    root, bench = tiny_root
    args = bench_run.parse_args(["--workload", tiny.CELL, "--seed",
                                 str(SEED), "--seconds", "1"])
    return bench_run.run_cell(args, root=root, require_tpu=False,
                              bench=bench, t0=0.0, **faults)


def test_tiny_cell_is_correct(tiny_root):
    res = _run(tiny_root)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "step_hbm_gb", "setup_s"}
    assert list(res)[-1] == "checks"


def _unchanged(fn):
    def step(state, batch, key):
        return state, fn(state, batch, key)[1]
    return step


def _half_batch(loss):
    return lambda p, b: loss(p, reference.half(b))


def _altered(fn):
    def step(state, batch, key):
        new, metrics = fn(state, batch, key)
        return new, dict(metrics, loss=metrics["loss"] * 1.05)
    return step


@pytest.mark.parametrize("fault", [
    {"step_wrap": _unchanged},       # the step returns its state unchanged
    {"loss_wrap": _half_batch},      # half the batch left out
    {"step_wrap": _altered},         # the answer altered where produced
], ids=["state_unchanged", "half_batch", "answer_altered"])
def test_planted_fault_is_not_correct(tiny_root, fault):
    assert not _run(tiny_root, **fault)["correct"]


def test_control_is_not_correct(tiny_root):
    """The reference at float8 products in the program's place fails the
    cell's limits."""
    root, bench = tiny_root
    cell = find_cell(tiny.CELL, root=root, bench=bench)
    sizes, traffic = cell.config["sizes"], cell.traffic
    feed = worker_feed(traffic, sizes["vocab_size"], sizes["d_model"], SEED)
    batches = [next(feed) for _ in range(bench_run.COMPARED_STEPS)]
    key = program.seed_key(SEED)
    want = reference.run(cell.reference, sizes, traffic, key, batches)
    got = reference.run(cell.reference, sizes, traffic, key, batches,
                        mode="fp8")
    ok, checks = compare.verdict(compare.numbers(got, want), cell.limits)
    assert not ok, checks


def _cli(cwd, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "7", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_real_cell_refuses_to_run_off_tpu():
    out = _cli(ROOT, "smollm-360m.nnm_cwtm.n4")
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_alone_does_not_run(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _cli(tmp_path, "smollm-360m.nnm_cwtm.n4")
    assert out.returncode != 0 and out.stdout.strip() == ""
