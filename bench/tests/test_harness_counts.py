"""Operation and byte counts against hand counts; the peaks table."""

import json

import pytest

from bench import work
from bench.cells import ROOT


def test_gram_counts_by_hand():
    # 2·N²·P multiply-adds counted as two operations; X read, G written, float32
    flops, nbytes = work.gram(787, 1900)
    assert flops == 2 * 787 * 787 * 1900
    assert nbytes == 4 * (787 * 1900 + 787 * 787)


@pytest.mark.parametrize("b", [1, 1000])
def test_binary_eval_counts_by_hand(b):
    n, k, m = 787, 10, 78
    flops, nbytes = work.binary_eval(n, k, m, b)
    # H·y; forward and back substitution (m²/2 multiply-adds each) per fold;
    # the train block H_{Tr,Te}·ė_Te per fold
    hand = 2 * n * n * b + k * 2 * (m * m) * b + k * 2 * (n - m) * m * b
    assert flops == hand
    assert nbytes == 4 * (n * n + k * m * m + k * (n - m) * m + n * b + b)


def test_least_time_takes_the_larger_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000.0, 50.0, peak) == (10.0, "compute")
    assert work.least_time(100.0, 50.0, peak) == (5.0, "memory")


def test_peaks_table_has_the_v5e_with_its_source():
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    row = table["kinds"]["TPU v5 lite"]
    assert row["flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert row["hbm_bytes"] == 16e9


def test_unknown_device_kind_is_an_error():
    import bench.run as brun

    with pytest.raises(KeyError):
        brun.peak_row("TPU v0 imaginary")
