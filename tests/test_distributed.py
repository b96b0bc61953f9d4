"""Multi-device shard_map / pjit tests.

Run in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the 8 fake devices never leak into this process (smoke tests and
benchmarks must see the single real CPU device).
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

_WORKER = Path(__file__).parent / "distributed_worker.py"
_SRC = str(Path(__file__).parent.parent / "src")


@pytest.mark.timeout(900)
def test_distributed_suite():
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)   # the worker sets its own
    proc = subprocess.run(
        [sys.executable, str(_WORKER)], env=env, capture_output=True,
        text=True, timeout=850)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    assert "ALL_OK" in proc.stdout, out[-4000:]
    for name in ("distributed_gram", "distributed_gram_under_jit", "distributed_hat",
                 "distributed_permutation_null", "searchlight_shape",
                 "sharded_train_loss_matches", "elastic_restore_values",
                 "elastic_restore_mesh"):
        assert f"PASS {name}" in proc.stdout, f"missing PASS {name}\n" + out[-2000:]
