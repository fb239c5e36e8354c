"""A tiny cell for the CPU tests: SmolLM's architecture at toy widths,
the NNM + CWTM mix with the Pallas kernels (interpreted off the chip),
and the limits of the real SmolLM cell."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
REAL = "smollm-360m.nnm_cwtm.n4"
CELL = "tiny-lm.nnm_cwtm"

SIZES = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
         "d_ff": 128, "vocab_size": 256, "head_dim": 16}


def make_root(tmp: Path) -> tuple[Path, dict]:
    """A checkout-like root holding only the tiny cell's files; returns
    (root, benchmark dict)."""
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    (tmp / "bench" / "limits").mkdir()
    shutil.copytree(BENCH / "metrics", tmp / "bench" / "metrics")
    shutil.copy(BENCH / "configs" / "smollm-360m.py",
                tmp / "bench" / "configs" / "tiny-lm.py")
    cfg = json.loads((BENCH / "configs" / "smollm-360m.json").read_text())
    cfg.update(name="tiny-lm", sizes=dict(cfg["sizes"], **SIZES))
    (tmp / "bench" / "configs" / "tiny-lm.json").write_text(json.dumps(cfg))
    traffic = json.loads(
        (BENCH / "traffic" / "text.nnm_cwtm.n4.json").read_text())
    traffic.update(backend="pallas", seq=16, corpus_rows=4000)
    (tmp / "bench" / "traffic" / "tiny.json").write_text(json.dumps(traffic))
    shutil.copy(BENCH / "limits" / f"{REAL}.json",
                tmp / "bench" / "limits" / f"{CELL}.json")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-lm", "source": "tiny",
                         "file": "bench/configs/tiny-lm.json", "reduced": [],
                         "why": "tiny"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny-lm",
                           "traffic": "tiny", "chips": 1, "why": "tiny"}]
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    return tmp, bench
