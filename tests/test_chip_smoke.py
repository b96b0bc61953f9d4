"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
one-chip and four-chip phases run end to end on CPU devices at a tiny size.

The phases run in subprocesses: they need the chip path's dtype policy
(x64 off, which the suite turns on) and, for four devices, their own
``XLA_FLAGS``. The TPU check is bypassed only here, by calling the phase
functions directly; the script itself has no option to skip it.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _run(args, *, cwd=ROOT, extra_env=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=timeout,
    )


def _phase(call: str, **kw):
    code = f"import chip_smoke as cs; print('SUMMARY', cs.{call})"
    return _run(["-c", code], **kw)


def test_refuses_to_run_without_a_tpu():
    proc = _run([str(SMOKE)])
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_fails_without_the_rest_of_the_repo(tmp_path):
    shutil.copy(SMOKE, tmp_path / SMOKE.name)
    proc = _run([SMOKE.name], cwd=tmp_path, extra_env={"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.timeout(600)
def test_one_chip_phase_runs_on_cpu_at_a_tiny_size():
    proc = _phase("one_chip(n_trials=60, n_perm=64)")
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    assert "every workload kind answered over HTTP and Client" in proc.stdout
    assert "on warm replay: 0" in proc.stdout
    assert "plan h dtype float32" in proc.stdout
    assert "SUMMARY" in proc.stdout


@pytest.mark.timeout(600)
def test_four_chip_phase_runs_on_four_cpu_devices():
    proc = _phase(
        "four_chip(n_trials=60, n_perm=64)",
        extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    assert "null sharding" in proc.stdout
    assert "SUMMARY" in proc.stdout


def test_compile_cache_defaults_to_the_checkout():
    assert compile_cache.CHECKOUT_CACHE_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_compile_cache_follows_the_environment(tmp_path):
    cache = tmp_path / "cache"
    code = (
        "import json, jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import setup_compilation_cache\n"
        "path = setup_compilation_cache()\n"
        "jax.jit(lambda v: v * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
        "print(json.dumps([path, jax.config.jax_compilation_cache_dir]))\n"
    )
    proc = _run(["-c", code], extra_env={"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert proc.returncode == 0, proc.stderr[-2000:]
    path, configured = json.loads(proc.stdout.strip().splitlines()[-1])
    assert path == configured == str(cache)
    assert any(cache.iterdir())
