"""Find a cell's configuration, traffic mix, limits, data, steps and metric readers by name.

``BENCHMARK.json`` at the checkout root lists the cells. Each piece of a
cell is a file of its own, so a later cell or metric is added with new
files and entries only:

* ``bench/configs/<config>.json`` — the deployment: data shape, folds, λ,
  dtype and precision, the engine's settings, chips;
* ``bench/data/<generator>.py`` — the data generator that the
  configuration's ``data`` block names (``"generator"``):
  ``simulate(seed, data, num_classes) -> (x, y)``, where ``x`` may have
  leading grid axes before its last two, ``(N, P)``;
* ``bench/traffic/<traffic>.json`` — the mix, read by :mod:`bench.loops`;
* ``bench/steps/<step>.py`` — one step of a closed loop's visit, for each
  step the mix names: ``run(client, engine, subj, step, seed)`` returns the
  product fields the step adds to the visit, and a step that produces
  answers has ``reference(subj, prod, rng, traffic, control)``, which
  returns ``{number: (count, per)}`` for :func:`bench.check.check_closed`;
* ``bench/limits/<cell>.json`` — the limit of each number that decides
  ``correct``, with the readings it was set from;
* ``bench/metrics/<metric>.py`` — one reader per metric, ``read(rec)``.

:func:`resolve` reads every one of them under the ``root`` it is given, so
a cell can be resolved from a copy of the tree.
"""

from __future__ import annotations

import dataclasses
import functools
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
    generator: ModuleType
    steps: dict  # step name -> step module, for every step the mix names


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    return json.loads(path.read_text())


@functools.cache
def _load_module(path: Path, what: str, needs: tuple) -> ModuleType:
    """The module at ``path``, loaded once per process; ``needs`` are the
    functions it must define."""
    if not path.is_file():
        raise FileNotFoundError(f"no {what}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{what.split()[0]}_{path.stem.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    for fn in needs:
        if not callable(getattr(module, fn, None)):
            raise TypeError(f"{path} defines no {fn}()")
    return module


def _load_reader(name: str, bench: Path) -> ModuleType:
    return _load_module(bench / "metrics" / f"{name}.py", f"reader for metric {name!r}",
                        ("read",))


def _load_generator(data: dict, bench: Path) -> ModuleType:
    if "generator" not in data:
        raise KeyError("the configuration's data block names no generator")
    name = data["generator"]
    return _load_module(bench / "data" / f"{name}.py", f"generator {name!r}", ("simulate",))


def _load_steps(traffic: dict, bench: Path) -> dict:
    names = [s["step"] for s in traffic.get("setup_steps", []) + traffic.get("visit", [])]
    return {n: _load_module(bench / "steps" / f"{n}.py", f"step {n!r}", ("run",))
            for n in dict.fromkeys(names)}


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell called ``name`` in ``root``'s ``BENCHMARK.json``, with all its files loaded."""
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
    return Cell(name, int(entry["chips"]), config, traffic, limits, e2e, per_layer, readers,
                _load_generator(config["data"], bench), _load_steps(traffic, bench))
