"""Benchmark tests: run as the chip does, in float32, with the checkout on the path.

The repository's own test suite turns on 64-bit mode for the whole
process; the benchmark's runs never do, so each test here runs with it
off and restores it after.
"""

import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def float32_like_the_chip():
    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", before)


@pytest.fixture
def tiny_cell():
    """Resolve a cell at a size a CPU test can hold: 120 trials, 76 channels
    (P > N, so plans stay dual as at full size), at most 3 subjects, each
    warmed once."""
    from bench import cells

    def make(name: str, **traffic):
        cell = cells.resolve(name)
        cell.config["data"].update(n_trials=120, n_channels=76)
        cell.traffic["subjects"] = min(int(cell.traffic["subjects"]), 3)
        cell.traffic.update(traffic)
        if "warm_visits" in cell.traffic:
            cell.traffic["warm_visits"] = min(cell.traffic["warm_visits"],
                                              cell.traffic["subjects"])
        return cell

    return make
