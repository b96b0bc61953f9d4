"""Find a cell's configuration, traffic mix, limits and metric readers by name.

``BENCHMARK.json`` at the checkout root lists the cells. Each piece of a
cell is a file of its own, so a later cell or metric is added with new
files and entries only:

* ``bench/configs/<config>.json`` — the deployment: data shape, folds, λ,
  dtype and precision, the engine's settings, chips;
* ``bench/traffic/<traffic>.json`` — the mix, read by :mod:`bench.loops`;
* ``bench/limits/<cell>.json`` — the limit of each number that decides
  ``correct``, with the readings it was set from;
* ``bench/metrics/<metric>.py`` — one reader per metric, ``read(rec)``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType

__all__ = ["ROOT", "Cell", "load_benchmark", "resolve"]

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    readers: dict  # metric name -> reader module


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    return json.loads(path.read_text())


def _load_reader(name: str, bench: Path) -> ModuleType:
    path = bench / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if not callable(getattr(module, "read", None)):
        raise TypeError(f"{path} defines no read(rec)")
    return module


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell called ``name`` in ``BENCHMARK.json``, with all its files loaded."""
    bench_file = load_benchmark(root)
    cells = {w["name"]: w for w in bench_file["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    entry = cells[name]
    bench = root / "bench"
    config = _load_json(bench / "configs" / f"{entry['config']}.json")
    traffic = _load_json(bench / "traffic" / f"{entry['traffic']}.json")
    limits = _load_json(bench / "limits" / f"{name}.json")
    e2e = [m for m in bench_file["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    per_layer = [m for m in bench_file["per_layer"] if name in m["workloads"]]
    readers = {m["name"]: _load_reader(m["name"], bench) for m in e2e + per_layer}
    return Cell(name, int(entry["chips"]), config, traffic, limits, e2e, per_layer, readers)
