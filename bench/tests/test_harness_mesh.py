"""The four-chip cell drives the mesh path end to end at a tiny size, on four
CPU devices.

``run_cell`` runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (this process keeps
one CPU device), on the cell as ``tiny_cell`` sizes it, with ``n_perm``
shrunk here so the CPU gets through several visits in its window.
"""

import json
import os
import subprocess
import sys

from bench.cells import ROOT

CELL = "eeg_binary_p3800_v5e4.perm10k"

_RUN = """
import json, sys
import jax
jax.config.update("jax_enable_x64", False)
import bench.run as brun
from bench import cells
sized = json.loads(sys.argv[1])
cell = cells.resolve(sized["name"])
cell.config, cell.traffic = sized["config"], sized["traffic"]
out = {trace: brun.run_cell(cell, 4294967301, 1.0, trace) for trace in (False, True)}
print(json.dumps({"devices": len(jax.devices()), "untraced": out[False], "traced": out[True]}))
"""


def test_four_chip_cell_runs_correct_on_four_cpu_devices(tiny_cell):
    cell = tiny_cell(CELL)
    cell.traffic["visit"][0]["n_perm"] = 1000
    sized = {"name": CELL, "config": cell.config, "traffic": cell.traffic}
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    proc = subprocess.run([sys.executable, "-c", _RUN, json.dumps(sized)], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4
    for trace, metrics in (("untraced", cell.end_to_end), ("traced", cell.per_layer)):
        res = got[trace]
        assert res["correct"], res["checks"]
        assert res["failed"] == 0 and res["attempted"] > 0
        assert res["window_compiles"] == 0
        missing = {m["name"] for m in metrics} - set(res["metrics"])
        # the roofline share needs a chip's peak; every other metric is read
        assert missing <= {"null_roofline.perm4"}, missing
    assert got["traced"]["metrics"]["collective_ms.perm4"]["value"] > 0
