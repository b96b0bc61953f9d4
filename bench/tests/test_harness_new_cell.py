"""A cell whose data, visit step, limits and reader are new files runs from those files alone.

``fixtures/new_cell/`` is laid out like ``bench/``: a configuration whose
generator makes a (Q, N, P) grid (a channel vector per time point), a
mix whose visit is one ``grid`` step through ``Workload(kind="grid")``,
checked point by point against a float64 refit, its limits and one
reader. ``fixtures/new_cell.json`` holds the entries a PR would add to
``BENCHMARK.json``. The test copies the checkout's benchmark, lays the
fixture over the copy, adds the entries, and runs the cell from the copy:
no file the benchmark already had changes but ``BENCHMARK.json``.
"""

import json
import shutil
from pathlib import Path

import numpy as np

import bench.run as brun
from bench import cells, check
from bench.cells import ROOT

FIXTURES = Path(__file__).resolve().parent / "fixtures"
CELL = "tiny_timegrid.grid_tiny"


def _files(root: Path) -> dict:
    return {p.relative_to(root).as_posix(): p.read_bytes() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_grid_cell_runs_correct_from_added_files_alone(tmp_path):
    root = tmp_path / "tree"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    had = _files(root / "bench")
    shutil.copytree(FIXTURES / "new_cell", root / "bench", dirs_exist_ok=True)
    added = set(_files(FIXTURES / "new_cell"))
    assert added and not added & set(had), "the fixture only adds files"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for group, entries in json.loads((FIXTURES / "new_cell.json").read_text()).items():
        bench[group] += entries
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))
    before = _files(root)

    cell = cells.resolve(CELL, root)
    for module in [cell.generator, *cell.steps.values(), *cell.readers.values()]:
        assert Path(module.__file__).is_relative_to(root)
    keep = {}
    res = brun.run_cell(cell, 4294967301, 1.0, False, keep=keep)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"grid_points_per_s", "setup_s"}
    shapes = keep["rec"]["shapes"]
    data = cell.config["data"]
    q = int(round((data["t_max"] - data["t_min"]) * data["fs"])) + 1
    assert shapes["grid"] == [q] and shapes["p"] == data["n_channels"]
    assert keep["products"][0]["grid"].shape == (q,)

    # the per-point check bites: accuracies moved by a tenth at every point fail it
    moved = [dict(p, grid=np.abs(p["grid"] - 0.1)) for p in keep["products"]]
    numbers = check.check_closed(cell, keep["subjects"], moved, 4294967301)
    assert numbers["flips_per_1k"] > cell.limits["flips_per_1k"]["limit"]

    after = _files(root)
    assert after == before, "the run changed no file of the copy"
    assert {k: v for k, v in after.items() if k.startswith("bench/")} == {
        **{f"bench/{k}": v for k, v in had.items()},
        **{f"bench/{k}": (FIXTURES / "new_cell" / k).read_bytes() for k in added}}
