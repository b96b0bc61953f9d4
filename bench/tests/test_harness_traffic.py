"""Each traffic mix drives the served path end to end at a tiny size on the CPU.

The chip check of ``bench/run.py`` is bypassed by calling ``run_cell``
directly; everything after it runs as on the chip: simulation, set-up,
the window, the reference check and the metric readers.
"""

import numpy as np
import pytest

import bench.run as brun
from bench import labels

CELLS = ["eeg_binary_p3800.perm1k", "eeg_3class_p1900.group16", "eeg_binary_p3800.http_cv"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_correct_at_a_tiny_size(tiny_cell, name, trace):
    cell = tiny_cell(name, rate_per_s=100.0)
    keep = {}
    res = brun.run_cell(cell, 4294967301, 1.0, trace, keep=keep)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["window_compiles"] == 0
    wanted = cell.per_layer if trace else cell.end_to_end
    missing = {m["name"] for m in wanted} - set(res["metrics"])
    # roofline shares need a chip's peak; every other metric is read
    assert {m for m in missing if "roofline" not in m} == set()
    assert list(res)[-1] == "checks"
    if trace:
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]


def test_same_seed_same_work(tiny_cell):
    cell = tiny_cell("eeg_binary_p3800.perm1k")
    a = brun.make_subjects(cell, 17)
    b = brun.make_subjects(cell, 17)
    np.testing.assert_array_equal(np.asarray(a[0].x), np.asarray(b[0].x))
    np.testing.assert_array_equal(a[0].te, b[0].te)


def test_open_loop_schedule_and_labels():
    a = labels.arrival_offsets(1, 200.0, 10.0)
    b = labels.arrival_offsets(2**31 + 5, 200.0, 10.0)
    assert a.size == b.size == 2000
    # the same gaps in another order: same load for every seed
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0)), np.sort(np.diff(b, prepend=0)))
    assert 9.0 < a[-1] < 11.0
    y = labels.random_split(5, 3, 787)
    assert y.dtype == np.float32 and (y > 0).sum() == 394 and (y < 0).sum() == 393
    np.testing.assert_array_equal(y, labels.random_split(5, 3, 787))
    assert not np.array_equal(y, labels.random_split(5, 4, 787))
