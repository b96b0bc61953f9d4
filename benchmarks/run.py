"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus a header comment per
section). ``--fast`` runs a reduced sweep (CI-sized); ``--json PATH``
additionally writes the rows (tagged with their section, the git SHA and
a UTC timestamp, so archived artifacts line up into a real trajectory)
as a JSON artifact — CI's bench-smoke job uploads this per PR and gates
warm-row latencies against ``benchmarks/baseline.json`` via
``benchmarks/compare.py``.

  bench_complexity  — paper Table 1 (empirical scaling exponents)
  bench_cv          — paper Fig. 3a left  (binary CV rel. efficiency)
  bench_perm        — paper Fig. 3a right (binary permutations)
  bench_multiclass  — paper Fig. 3b       (multi-class CV + permutations)
  bench_eeg         — paper Fig. 4        (EEG/MEG-style permutation run)
  bench_kernels     — CV hot-spot kernels (XLA path GFLOP/s)
  bench_serve       — serving engine cold/warm + batch throughput
  bench_store       — plan-store write/load + cold-boot-with-store payoff
  bench_update      — incremental plan updates vs rebuild; sliding window
  bench_rsa         — RSA serving cold/warm + pairdist kernel
  bench_async       — async server: concurrent clients, streaming chunks
  bench_http        — HTTP/SSE edge: wire overhead, gather, first chunk
  bench_latency     — warm per-stage latency budget (tracing-derived)
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys

import jax

jax.config.update("jax_enable_x64", True)

from benchmarks import (
    bench_async,
    bench_complexity,
    bench_cv,
    bench_eeg,
    bench_http,
    bench_kernels,
    bench_latency,
    bench_multiclass,
    bench_perm,
    bench_rsa,
    bench_serve,
    bench_store,
    bench_update,
)
from benchmarks.common import print_rows

MODULES = [
    ("complexity(Table1)", bench_complexity),
    ("cv(Fig3a-left)", bench_cv),
    ("perm(Fig3a-right)", bench_perm),
    ("multiclass(Fig3b)", bench_multiclass),
    ("eeg(Fig4)", bench_eeg),
    ("kernels", bench_kernels),
    ("serve(engine)", bench_serve),
    ("store(plan-store)", bench_store),
    ("update(incremental)", bench_update),
    ("rsa(serve+kernel)", bench_rsa),
    ("async(serve.aio)", bench_async),
    ("http(serve.http)", bench_http),
    ("latency(stage-budget)", bench_latency),
]


def _git_sha() -> str:
    sha = os.environ.get("GITHUB_SHA")
    if sha:
        return sha
    try:
        proc = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True,
            text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10,
        )
        return proc.stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="reduced CI sweep")
    ap.add_argument(
        "--only", default=None, help="comma-separated substring filter on section names"
    )
    ap.add_argument(
        "--json", default=None, metavar="PATH", help="also write rows as a JSON artifact"
    )
    args = ap.parse_args()

    sha = _git_sha()
    stamp = datetime.datetime.now(datetime.timezone.utc).isoformat(timespec="seconds")
    all_rows = []
    print("name,us_per_call,derived")
    for name, mod in MODULES:
        if args.only and not any(s in name for s in args.only.split(",")):
            continue
        print(f"# --- {name} ---", file=sys.stderr)
        rows = mod.run(fast=args.fast)
        print_rows(rows)
        all_rows.extend(dict(section=name, git_sha=sha, timestamp=stamp, **r) for r in rows)

    if args.json:
        devices = jax.devices()
        meta = {
            "backend": jax.default_backend(),
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "fast": bool(args.fast),
            "jax": jax.__version__,
            "git_sha": sha,
            "timestamp": stamp,
        }
        with open(args.json, "w") as fh:
            json.dump({"meta": meta, "rows": all_rows}, fh, indent=2)
        print(f"# wrote {len(all_rows)} rows to {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
