"""The readers of the program's own spans, on made-up records.

A record from a program without the spans (no ``executor_wait`` or ``gc``
stage, no ``repro.<stage>`` gap) reads None, so the result line leaves the
metric out instead of failing.
"""

import pytest

from bench import cells

HTTP, PERM, GROUP = (
    "eeg_binary_p3800.http_cv", "eeg_binary_p3800.perm1k", "eeg_3class_p1900.group16")


def reader(cell: str, metric: str):
    return cells.resolve(cell).readers[metric].read


def _stages(**sums):
    return {name: {"count": count, "sum_s": sum_s} for name, (count, sum_s) in sums.items()}


def test_executor_wait_per_request():
    read = reader(HTTP, "executor_wait_ms.http")
    rec = {"requests": 640, "stages": _stages(executor_wait=(640, 0.32), decode=(640, 1.0))}
    assert read(rec) == pytest.approx(0.5)
    assert read({"requests": 640, "stages": _stages(decode=(640, 1.0))}) is None  # no such stage
    assert read({"requests": 640, "stages": _stages(executor_wait=(0, 0.0))}) is None
    assert read({"requests": 640}) is None  # an untraced run


def test_gc_pause_per_second_of_window():
    read = reader(HTTP, "gc_ms_per_s.http")
    rec = {"window_s": 10.0, "stages": _stages(gc=(31, 0.05))}
    assert read(rec) == pytest.approx(5.0)
    assert read({"window_s": 10.0, "stages": _stages(gc=(0, 0.0))}) == 0.0  # no pause
    assert read({"window_s": 10.0, "stages": _stages(decode=(3, 0.1))}) is None
    assert read({"window_s": 10.0}) is None


_GAPS = [
    ["bench.permutation / repro.null_chunk", 0.9],
    ["bench.permutation", 0.4],
    ["bench.permutation / repro.validate", 0.1],
    ["bench.permutation / DeferredTpuAllocator::Allocate", 0.5],
    ["bench.cv / repro.encode", 0.2],
    ["outside bench spans / repro.gc", 0.05],
]


@pytest.mark.parametrize("cell,metric,count_key,scale", [
    (PERM, "engine_idle_ms_per_1k.perm", "draws", 1e6),
    (GROUP, "engine_idle_ms.group", "visits", 1e3),
])
def test_engine_idle_sums_gaps_under_program_spans(cell, metric, count_key, scale):
    read = reader(cell, metric)
    rec = {count_key: 1000, "device_trace": {"idle_gaps": _GAPS}}
    # runtime events deeper than the program span keep their own labels
    assert read(rec) == pytest.approx(scale * (0.9 + 0.1 + 0.2 + 0.05) / 1000)
    parent = {count_key: 1000, "device_trace": {"idle_gaps": [g for g in _GAPS
                                                               if "repro." not in g[0]]}}
    assert read(parent) is None
    assert read({count_key: 1000}) is None
    assert read({count_key: 0, "device_trace": {"idle_gaps": _GAPS}}) is None


@pytest.mark.parametrize("cell,metric,count_key", [
    (PERM, "engine_idle_ms_per_1k.perm", "draws"),
    (GROUP, "engine_idle_ms.group", "visits"),
])
def test_engine_idle_is_zero_when_spans_opened_and_no_gap_fell_in_one(cell, metric, count_key):
    read = reader(cell, metric)
    unnamed = {"idle_gaps": [g for g in _GAPS if "repro." not in g[0]]}
    spans = _stages(null_chunk=(3, 0.006), gc=(0, 0.0))
    assert read({count_key: 1000, "device_trace": unnamed, "stages": spans}) == 0.0
    none_opened = _stages(null_chunk=(0, 0.0), gc=(0, 0.0))
    assert read({count_key: 1000, "device_trace": unnamed, "stages": none_opened}) is None
    assert read({count_key: 1000, "stages": spans}) is None  # an untraced run
