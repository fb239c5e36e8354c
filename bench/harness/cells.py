"""Find a cell's files by name.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

  configuration  the ``file`` of its entry (sizes, JSON), and beside it
                 the plain reference of the same stem (``.py``)
  traffic mix    ``bench/traffic/<traffic>.json``
  cell limits    ``bench/limits/<cell>.json``
  metric reader  ``bench/metrics/<metric>.py``

A new configuration, mix, cell or metric is therefore a set of new files;
no file of the harness changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Optional

#: The checkout's root: ``bench/harness/cells.py`` -> ``.``
ROOT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    chips: int
    config: dict            # the configuration file's JSON
    reference: ModuleType   # the configuration's plain reference
    traffic: dict           # the traffic mix's JSON
    limits: dict            # {number: limit} that decide ``correct``
    end_to_end: tuple       # metric entries the cell reports, trace off
    per_layer: tuple        # metric entries the cell reports, trace on


def load_module(path: Path) -> ModuleType:
    """Import a file by path (names may hold '-' and '.')."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, *, root: Path = ROOT,
              bench: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` (or of ``bench``, a dict of
    the same form), with its configuration, reference, traffic and limits
    loaded from their files under ``root``."""
    bench = read_json(root / "BENCHMARK.json") if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_path = root / configs[w["config"]]["file"]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=read_json(cfg_path),
        reference=load_module(cfg_path.with_suffix(".py")),
        traffic=read_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        limits=read_json(root / "bench" / "limits" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
    )


def metric_reader(name: str, *, root: Path = ROOT) -> ModuleType:
    """The reader module of per-layer metric ``name``: ``read(ctx)``
    returns the metric's value, or None where the trace holds nothing it
    reads."""
    return load_module(root / "bench" / "metrics" / f"{name}.py")
