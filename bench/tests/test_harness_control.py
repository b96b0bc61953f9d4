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
fail them too.
"""

import pytest

import bench.run as brun
from bench import check
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
