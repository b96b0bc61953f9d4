#!/usr/bin/env python3
"""Readings that the limits of ``correct`` and the open loop's rate were set from.

    python bench/calibrate.py --workload <cell> --seeds 0-11 --control-seeds 100-102 \
        [--seconds 3] [--rates 40,60,80 --rate-runs 3]

Not part of a benchmark run. In one process on the chip, at the cell's own
size and load, with a short window:

* the program's numbers on every ``--seeds`` and ``--control-seeds`` seed
  (the lower readings);
* the control's numbers on every ``--control-seeds`` seed, each judged by
  :func:`bench.check.verdict` against the cell's limits: the float64
  reference's arithmetic with every contraction at one bfloat16 pass
  (:data:`bench.reference.refit.ONE_PASS`) put in the program's place on
  the label vectors that seed's window served, and the program with its
  own lower-precision path switched on (``precision="bf16_gram"``, the
  Gram from bfloat16 inputs), skipped with one line saying so where the
  engine has no such path (``gram_impl="distributed"``);
* with ``--one-pass-program``, instead of the above, the program itself
  with the TPU's default one-pass precision planted in place of the
  ``HIGHEST`` it gives every float32 contraction (its
  ``dot_precision``), on every ``--control-seeds`` seed: the step a later
  change would be tempted by, read in a process of its own, since JAX's
  caches of traced programs would otherwise keep the ``HIGHEST`` ones;
* with ``--rates``, ``--rate-runs`` runs of the open-loop cell at each rate:
  latency percentiles, the generator's lateness, failures, and the median
  latency of the last quarter of the window over the first's (a backlog
  that grows reads well above 1).

Prints one JSON object per reading.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

import numpy as np  # noqa: E402

import bench.run as brun  # noqa: E402
from bench import cells, check  # noqa: E402
from bench.reference import refit  # noqa: E402


def _seeds(spec: str) -> list:
    out = []
    for part in spec.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def one(cell, seed, seconds, kind, precision=None):
    keep = {}
    t0 = time.perf_counter()
    res = brun.run_cell(cell, seed, seconds, False, precision=precision, keep=keep)
    row = {"kind": kind, "seed": seed, "checks": {k: v["value"] for k, v in res["checks"].items()},
           "correct": res["correct"], "metrics": {k: v["value"] for k, v in res["metrics"].items()},
           "window_compiles": res["window_compiles"], "wall_s": time.perf_counter() - t0}
    print(json.dumps(row), flush=True)
    return keep


def bf16_gram_skip(cell):
    """Why the program's ``bf16_gram`` control cannot run in ``cell``, or None."""
    if cell.config["engine"].get("gram_impl") == "distributed":
        return "the engine has no bf16_gram path with gram_impl='distributed'"
    return None


def plant_one_pass() -> None:
    """Make every float32 contraction of the program one bfloat16 pass."""
    import importlib

    import jax
    from repro.kernels import common

    for name in ("core.fastcv", "core.multiclass", "core.distributed", "kernels.gram.ops",
                 "kernels.gram.gram", "kernels.foldsolve.ops", "kernels.fold_eval.fold_eval",
                 "kernels.hat_apply.hat_apply"):
        importlib.import_module(f"repro.{name}")

    def one_pass(dtype):
        return jax.lax.Precision.DEFAULT

    served = common.dot_precision
    for name, mod in list(sys.modules.items()):
        if name.startswith("repro") and getattr(mod, "dot_precision", None) is served:
            mod.dot_precision = one_pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--rate-runs", type=int, default=1)
    ap.add_argument("--one-pass-program", action="store_true")
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    brun.tpu_devices(cell.chips)
    brun.setup_compilation_cache()
    if args.one_pass_program:
        plant_one_pass()
        for seed in _seeds(args.control_seeds):
            one(cell, seed, args.seconds, "control_program_one_pass")
        return 0
    for seed in _seeds(args.seeds):
        one(cell, seed, args.seconds, "program")
    skip = bf16_gram_skip(cell)
    if skip and args.control_seeds:
        print(json.dumps({"kind": "control_bf16_gram", "skipped": skip}), flush=True)
    for seed in _seeds(args.control_seeds):
        keep = one(cell, seed, args.seconds, "program")
        if keep["products"] is not None:
            numbers = check.check_closed(cell, keep["subjects"], keep["products"], seed,
                                         control=refit.ONE_PASS)
        else:
            numbers = check.check_open(keep["subjects"][0], keep["sampled"],
                                       control=refit.ONE_PASS)
        numbers["failed"] = keep["rec"]["failed"]
        correct, _ = check.verdict(numbers, cell.limits)
        print(json.dumps({"kind": "control_one_pass", "seed": seed, "checks": numbers,
                          "correct": correct}), flush=True)
        if not skip:
            one(cell, seed, args.seconds, "control_bf16_gram", precision="bf16_gram")
    for rate in [float(r) for r in args.rates.split(",") if r]:
        cell.traffic["rate_per_s"] = rate
        for run in range(args.rate_runs):
            keep = {}
            res = brun.run_cell(cell, 1000 * run + int(rate), args.seconds, False, keep=keep)
            rec = keep["rec"]
            lat = np.asarray(rec["latencies_ms"])
            q = max(1, lat.size // 4)
            print(json.dumps({
                "kind": "rate", "rate_per_s": rate, "run": run, "requests": int(lat.size),
                "failed": res["failed"], "correct": res["correct"],
                "p50_ms": float(np.percentile(lat, 50)), "p95_ms": float(np.percentile(lat, 95)),
                "p99_ms": float(np.percentile(lat, 99)),
                "late_p95_ms": float(np.percentile(rec["late_ms"], 95)),
                "backlog_ratio": float(np.median(lat[-q:]) / np.median(lat[:q])),
                "window_s": rec["window_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
