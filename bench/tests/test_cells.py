"""The harness finds every piece of a cell by its name, so a new
configuration, traffic mix, cell or per-layer metric is new files only."""
import json
import shutil

import pytest

from harness import compare
from harness.cells import ROOT, find_cell, metric_reader

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_its_files(name):
    cell = find_cell(name)
    assert set(cell.limits) == set(compare.NUMBERS)
    for fn in ("param_specs", "loss", "matmul_positions",
               "attention_flops_per_row"):
        assert callable(getattr(cell.reference, fn))
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(metric_reader(metric).read)


def test_a_new_cell_is_new_files_only(tmp_path):
    """Copy the tree, add a configuration, a mix, a cell and a metric as
    files and a BENCHMARK entry each: the harness finds them all."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench")
    cfg = root / "bench" / "configs"
    shutil.copy(cfg / "smollm-360m.json", cfg / "new-model.json")
    shutil.copy(cfg / "smollm-360m.py", cfg / "new-model.py")
    tr = json.loads((root / "bench/traffic/text.cwtm.n4.json").read_text())
    (root / "bench/traffic/new-mix.json").write_text(
        json.dumps(dict(tr, batch=2)))
    shutil.copy(root / "bench/limits/smollm-360m.cwtm.n4.json",
                root / "bench/limits/new-model.new-mix.json")
    (root / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return None\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "new-model", "source": "x",
                             "file": "bench/configs/new-model.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-model.new-mix",
                               "config": "new-model", "traffic": "new-mix",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "kernels", "moves": "tokens_per_s",
                               "workloads": ["new-model.new-mix"]})
    cell = find_cell("new-model.new-mix", root=root, bench=bench)
    assert cell.traffic["batch"] == 2
    assert "new_metric" in [m["name"] for m in cell.per_layer]
    assert metric_reader("new_metric", root=root).read(None) is None
    old = find_cell(CELLS[0], root=root, bench=bench)
    assert "new_metric" not in [m["name"] for m in old.per_layer]
