"""Restart-durability smoke against a *live* ``serve_cv --http`` server.

Two phases against the same ``--state-dir``, bracketing a ``kill -9``:

    # boot A: JAX_COMPILATION_CACHE_DIR=xcache, --plan-store pstore --save-plans
    python benchmarks/restart_smoke.py --phase warm --url $URL --state-dir st
    kill -9 $SERVER_PID
    # boot B: same dirs + --warmup-from st/traffic.json
    python benchmarks/restart_smoke.py --phase restart --url $URL \\
        --state-dir st --json restart-smoke.json

``warm`` registers a deterministic dataset, submits one workload per
warmed estimator family, records the traffic client-side (SIGKILL never
reaches the server's ``--record-traffic`` dump) and snapshots every
response bit-exactly. ``restart`` then proves the rebooted process
reached steady state *from disk alone*:

  * ``plans_built == 0`` — every plan (boot warm-up replays and first
    wire traffic) was loaded from the plan store, never rebuilt;
  * ``store_hits > 0`` and zero quarantined entries — the loads were
    verified reads, not silent cache misses;
  * ``compile_events`` stays flat across first wire traffic — the
    ``--warmup-from`` replay plus the persistent XLA compilation cache
    covered every program this traffic needs;
  * every response is **bit-identical** to its pre-kill snapshot.

Exit status: 0 conformant, 1 any restart-durability regression.
"""

from __future__ import annotations

import argparse
import datetime
import json
import pathlib
import re
import sys
import time

import jax

# A client of a separate serve_cv process: stay off the accelerator (one
# process owns a chip) and keep the server's float32 dtype policy, so the
# bit-identical checks compare like with like.
jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np

from benchmarks.common import row
from repro.core import folds as foldlib
from repro.data import synthetic
from repro.serve import HTTPClient, Workload
from repro.serve.batching import DEFAULT_BUCKETS
from repro.serve.http import response_to_dict
from repro.serve.workload import TrafficLog

EXPECTED = "expected.json"
TRAFFIC = "traffic.json"


def _wait_healthy(client: HTTPClient, timeout_s: float) -> float:
    t0 = time.monotonic()
    deadline = t0 + timeout_s
    while True:
        try:
            if client.healthz().get("status") == "ok":
                return time.monotonic() - t0
        except Exception:  # noqa: BLE001 - server still booting
            pass
        if time.monotonic() > deadline:
            raise SystemExit(f"server not healthy after {timeout_s:.0f}s")
        time.sleep(0.25)


def _compile_events(client: HTTPClient) -> int:
    m = re.search(r"^compile_events (\d+)$", client.metrics_text(), re.M)
    assert m, "compile_events missing from /v1/metrics"
    return int(m.group(1))


def _register(client: HTTPClient, args):
    """Deterministic dataset + the workload set both phases replay."""
    x, yc = synthetic.make_classification(
        jax.random.PRNGKey(7), args.n, args.p, num_classes=3, class_sep=2.0
    )
    y = jnp.where(yc % 2 == 0, -1.0, 1.0)
    folds = foldlib.kfold(args.n, args.k, seed=3)
    handle = client.register(
        np.asarray(x), (np.asarray(folds.te_idx), np.asarray(folds.tr_idx)), args.lam
    )
    workloads = [
        ("cv/binary", Workload(kind="cv", dataset=handle, y=y)),
        ("cv/ridge", Workload(kind="cv", dataset=handle, y=y, estimator="ridge")),
        (
            "cv/multiclass",
            Workload(kind="cv", dataset=handle, y=yc, estimator="multiclass", num_classes=3),
        ),
        (
            "permutation",
            Workload(kind="permutation", dataset=handle, y=y, n_perm=args.perm, seed=11),
        ),
    ]
    return handle, workloads


def _canon(d: dict) -> dict:
    """JSON round-trip so in-memory and reloaded snapshots compare equal.

    Drops the tracing-only ``timings`` field (--metrics servers attach
    per-request stage latencies, which legitimately differ across boots);
    every conformance field — values, scores, nulls, plan_key — stays.
    """
    d = dict(d)
    d.pop("timings", None)
    return json.loads(json.dumps(d))


def phase_warm(client: HTTPClient, args, state: pathlib.Path) -> list[dict]:
    _, workloads = _register(client, args)
    log = TrafficLog()
    expected = {}
    for name, w in workloads:
        expected[name] = _canon(response_to_dict(client.submit(w)))
        # Client-side record: SIGKILL kills the server before its own
        # --record-traffic shutdown dump could ever run.
        log.record(w, DEFAULT_BUCKETS)
    (state / EXPECTED).write_text(json.dumps(expected, indent=2))
    log.save(state / TRAFFIC)
    print(
        f"[restart_smoke] warm: {len(expected)} responses snapshotted, "
        f"{len(log)} traffic entries -> {state}"
    )

    # Write-behind is async; poll until the store has absorbed the plan.
    deadline = time.monotonic() + 30.0
    while True:
        eng = client.stats()["engine"]
        if eng["store_writes"] >= 1:
            break
        if time.monotonic() > deadline:
            raise SystemExit("plan store absorbed no writes within 30s of traffic")
        time.sleep(0.25)
    print(
        f"[restart_smoke] warm: {eng['store_writes']} plan(s) persisted, "
        f"{eng['store_bytes'] / 2**20:.1f} MiB on disk — ready for kill -9"
    )
    return []


def phase_restart(client: HTTPClient, args, state: pathlib.Path, t_boot: float) -> list[dict]:
    expected = json.loads((state / EXPECTED).read_text())

    # Registration is content-addressed: the same bytes must resolve to
    # the same handle, or the plan store could never have matched.
    eng0 = client.stats()["engine"]
    compiles0 = _compile_events(client)
    _, workloads = _register(client, args)

    t_first = []
    for name, w in workloads:
        t0 = time.perf_counter()
        got = _canon(response_to_dict(client.submit(w)))
        t_first.append(time.perf_counter() - t0)
        assert got == expected[name], f"{name}: response differs from pre-kill snapshot"
    print(f"[restart_smoke] {len(workloads)} responses bit-identical across kill -9")

    eng = client.stats()["engine"]
    compiles = _compile_events(client)
    assert eng["plans_built"] == 0, (
        f"rebooted server rebuilt {eng['plans_built']} plan(s); "
        f"store: {eng['store_hits']} hits / {eng['store_misses']} misses"
    )
    assert eng["store_hits"] > 0, "restart served traffic without a single store hit"
    assert compiles == compiles0, (
        f"compile_events moved {compiles0} -> {compiles} on first post-restart "
        f"traffic; --warmup-from + compilation cache did not cover it"
    )
    print(
        f"[restart_smoke] steady state from disk: 0 plans built, "
        f"{eng['store_hits']} store hits, compile_events flat at {compiles} "
        f"(boot {eng0['store_hits']} hits before first wire traffic)"
    )

    def smoke_row(name, seconds, derived):
        return dict(section="restart-smoke", **row(name, seconds, derived))

    return [
        smoke_row(
            f"restart_boot_healthy_N{args.n}_P{args.p}",
            t_boot,
            f"kill -9 -> healthy with --warmup-from; {eng0['store_hits']} "
            f"plans from store at boot",
        ),
        smoke_row(
            f"restart_first_traffic_{len(workloads)}req",
            float(np.median(t_first)),
            f"median submit; 0 plan builds, compile_events flat at {compiles}",
        ),
    ]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--phase", required=True, choices=("warm", "restart"))
    ap.add_argument("--url", required=True, help="base URL of a serve_cv --http server")
    ap.add_argument(
        "--state-dir",
        required=True,
        help="directory carrying expected.json + traffic.json across the kill",
    )
    ap.add_argument("--json", default=None, metavar="PATH", help="latency artifact path")
    ap.add_argument("--n", type=int, default=96, help="samples (match server --n)")
    ap.add_argument("--p", type=int, default=256, help="features")
    ap.add_argument("--k", type=int, default=6, help="folds (match server --k)")
    ap.add_argument("--lam", type=float, default=1.0)
    ap.add_argument("--perm", type=int, default=64, help="permutation draws")
    ap.add_argument("--boot-timeout", type=float, default=180.0)
    args = ap.parse_args()

    state = pathlib.Path(args.state_dir)
    state.mkdir(parents=True, exist_ok=True)
    client = HTTPClient(args.url)
    t_boot = _wait_healthy(client, args.boot_timeout)
    print(f"[restart_smoke] {args.url} healthy after {t_boot:.2f}s ({args.phase} phase)")

    if args.phase == "warm":
        rows = phase_warm(client, args, state)
    else:
        rows = phase_restart(client, args, state, t_boot)

    for r in rows:
        print(f"[restart_smoke] {r['name']},{r['us_per_call']:.1f},{r['derived']}")
    if args.json and rows:
        meta = {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "url": args.url,
            "phase": args.phase,
            "timestamp": datetime.datetime.now(datetime.timezone.utc).isoformat(timespec="seconds"),
        }
        with open(args.json, "w") as fh:
            json.dump({"meta": meta, "rows": rows}, fh, indent=2)
        print(f"[restart_smoke] wrote {len(rows)} rows to {args.json}")
    print(f"[restart_smoke] {args.phase} OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
