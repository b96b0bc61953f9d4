"""The engine's four-device permutation path, on a (2, 2) mesh of CPU devices.

``tests/mesh_engine_worker.py`` runs once, in a subprocess with its own
``XLA_FLAGS`` (this process must keep one CPU device), and prints its
readings as JSON; each test here checks one of them.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

_WORKER = Path(__file__).parent / "mesh_engine_worker.py"
N, K = 60, 5  # the worker's trials and folds: K·m = 60 test trials


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the worker sets its own
    proc = subprocess.run([sys.executable, str(_WORKER)], env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_mapping_resolves_to_a_mesh_over_the_first_devices(readings):
    m = readings["mapping_mesh"]
    assert m["is_mesh"] and m["shape"] == {"data": 2, "model": 2} and m["devices"] == 4
    # a JSON list of axes arrives as a tuple, as a Mesh's shard_map specs need
    assert m["perm_axes"] == ["data", "model"] and m["perm_axes_tuple"]


def test_register_lays_features_over_the_model_axis(readings):
    assert readings["x_layout"] == {"spec": [None, "model"], "devices": 4}


def test_mesh_null_equals_the_one_device_null(readings):
    # same seed, same draws, and in float64 the same decisions: equal,
    # observed and p-value included
    r = readings["null"]
    assert len(r["mesh"]) == 50
    assert r["mesh"] == r["one"]
    assert r["observed"][0] == r["observed"][1] and r["p"][0] == r["p"][1]
    assert r["null_devices"] == 4  # the one program's packed test is on every device
    assert r["host"]  # and the response holds host arrays cut from it


def test_mesh_one_program_equals_the_mesh_streamed_null(readings):
    # the same test streamed in chunks on the mesh, each chunk a dispatch
    # of its own: the one program's null, observed metric and p-value
    r, s = readings["null"], readings["stream"]
    np.testing.assert_allclose(r["mesh"], s["null"], rtol=0, atol=1e-12)
    assert abs(r["observed"][0] - s["observed"]) < 1e-12
    assert abs(r["p"][0] - s["p"]) < 1e-12


def test_sampled_draws_match_the_float64_refit(readings):
    # accuracy is a count of the K·m test trials; the program reports it in
    # float32, so compare counts: no test trial may flip against the refit
    r = readings["refit"]
    ref = np.rint(np.asarray(r["reference"]) * N)
    got = np.rint(np.asarray(r["program"]) * N)
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_allclose(r["program"], r["reference"], rtol=0, atol=1e-6)


def test_second_analysis_of_the_same_shape_makes_no_program(readings):
    assert readings["second_analysis_programs"] == 0


def test_chunk_pads_to_whole_shards_and_counts_the_padding(readings):
    # 30 draws pad to 32 over four shards and come back as 30, equal to
    # the leading draws of the first analysis (prefix-stable draws)
    assert readings["chunk"] == {"size": 30, "equal": True}
    c = readings["counters"]
    # two analyses of 50 (bucket 64: 14 padding each) and the chunk of 30 (2)
    assert c["mesh_draws"] == 130 and c["mesh_pads"] == 30
    assert c["local_draws"] == 50 and c["local_pads"] == 14
    assert c["labels_evaluated"] == 130


def test_a_live_mesh_still_works(readings):
    r = readings["live_mesh"]
    assert r["same_mesh"]
    assert r["null"] == readings["null"]["one"]
