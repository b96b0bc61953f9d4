"""The control comes out not correct.

The configurations state float32 with every contraction at ``HIGHEST``.
The program has a lower-precision path of its own, ``precision="bf16_gram"``
(the Gram from bfloat16 inputs), and with it switched on the cell's own
limits must fail. At 240 trials × 152 channels (P = 1520 or 760) the
program's numbers sit well inside the limits and the control's well
outside, as at full size;
``bench/calibrate.py`` takes the same readings on the chip.

The reference's arithmetic with every contraction at one bfloat16 pass,
put in the program's place on the label vectors the window served, must
fail them too. Where the engine has no ``bf16_gram`` path (the
feature-sharded Gram of the four-chip cell), ``bench/calibrate.py`` skips
that control and says so.
"""

import json

import pytest

import bench.run as brun
from bench import calibrate, cells, check
from bench.reference import refit

CELLS = ["eeg_binary_p3800.perm1k", "eeg_3class_p1900.group16", "eeg_binary_p3800.http_cv"]


def _cell(tiny_cell, name):
    cell = tiny_cell(name, rate_per_s=100.0)
    cell.config["data"].update(n_trials=240, n_channels=152)
    cell.traffic["subjects"] = cell.traffic["warm_visits"] = min(cell.traffic["subjects"], 2)
    return cell


@pytest.mark.parametrize("name", CELLS)
def test_program_passes_and_its_bf16_gram_path_fails(tiny_cell, name):
    cell = _cell(tiny_cell, name)
    ok = brun.run_cell(cell, 2**31 + 3, 1.0, False)
    assert ok["correct"], ok["checks"]
    ctl = brun.run_cell(cell, 2**31 + 3, 1.0, False, precision="bf16_gram")
    assert not ctl["correct"], ctl["checks"]
    for k, c in ctl["checks"].items():
        if k != "failed":
            assert c["value"] > 3 * ok["checks"][k]["value"]


@pytest.mark.parametrize("name", CELLS)
def test_one_pass_refit_in_the_programs_place_fails(tiny_cell, name):
    cell = _cell(tiny_cell, name)
    keep = {}
    seed = 2**31 + 5
    prog = brun.run_cell(cell, seed, 1.0, False, keep=keep)
    assert prog["correct"], prog["checks"]
    if keep["products"] is not None:
        numbers = check.check_closed(cell, keep["subjects"], keep["products"], seed,
                                     control=refit.ONE_PASS)
    else:
        numbers = check.check_open(keep["subjects"][0], keep["sampled"], control=refit.ONE_PASS)
    numbers["failed"] = keep["rec"]["failed"]
    correct, rows = check.verdict(numbers, cell.limits)
    assert not correct, rows


def test_calibrate_skips_the_bf16_gram_control_where_the_gram_is_distributed(monkeypatch, capsys):
    four = "eeg_binary_p3800_v5e4.perm10k"
    assert "distributed" in calibrate.bf16_gram_skip(cells.resolve(four))
    assert all(calibrate.bf16_gram_skip(cells.resolve(name)) is None for name in CELLS)
    runs = []

    def one(cell, seed, seconds, kind, precision=None):
        runs.append((kind, precision))
        return {"products": [], "subjects": [], "rec": {"failed": 0}}

    monkeypatch.setattr(calibrate.brun, "tpu_devices", lambda chips: None)
    monkeypatch.setattr(calibrate.brun, "setup_compilation_cache", lambda: "")
    monkeypatch.setattr(calibrate, "one", one)
    monkeypatch.setattr(calibrate.check, "check_closed", lambda *a, **kw: {"flips_per_1k": 0.0})
    assert calibrate.main(["--workload", four, "--control-seeds", "1-3"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [line for line in lines if "skipped" in line] == [
        {"kind": "control_bf16_gram", "skipped": calibrate.bf16_gram_skip(cells.resolve(four))}]
    assert runs == [("program", None)] * 3
    assert [line["kind"] for line in lines].count("control_one_pass") == 3
