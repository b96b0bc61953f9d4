"""Wire-conformance smoke against a *live* ``serve_cv --http`` server.

    python -m repro.launch.serve_cv --http 8123 --warmup --pin &
    PYTHONPATH=src:. python benchmarks/http_smoke.py --url http://127.0.0.1:8123 \\
        --json http-smoke.json

CI's http-smoke job boots the server with ``--warmup`` and runs this
script against it, which asserts — across a real process boundary —
everything the in-process conformance suite (tests/test_http.py) pins:

  * all five compute workload kinds served over HTTP are **bit-identical** to a
    local in-process Client computing the same workloads;
  * streamed SSE permutation chunks concatenate to the exact monolithic
    null distribution;
  * the warmed eval families (binary/ridge/multiclass CV, permutation
    at the default chunk) serve first wire traffic with **0 compiles**
    (``--expect-warm``; proves ``--warmup`` covered real traffic), and a
    full warm replay of every kind adds 0 compiles;
  * ``POST /v1/datasets/{fp}/append`` advances the dataset version with
    zero compiles, and ``GET /v1/datasets`` + the per-dataset stats
    round-trip ``version``/``n_appended`` across the wire;
  * ``GET /v1/metrics`` renders parseable Prometheus text with every
    stage-latency histogram pre-declared, and ``compile_events`` stays
    flat across a scrape → warm submit → scrape cycle.

Latency percentiles land in a ``run.py --json``-shaped artifact next to
the bench-smoke one. Exit status: 0 conformant, 1 mismatch/regression.
"""

from __future__ import annotations

import argparse
import datetime
import json
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

from benchmarks.common import percentiles, row
from repro.core import folds as foldlib
from repro.data import synthetic
from repro.serve import Client, CVEngine, HTTPClient, Workload
from repro.serve.http import assert_responses_equal
from repro.serve.trace import STAGES

# Prometheus text format 0.0.4: HELP/TYPE comments + `name{labels} value`
# sample lines (same shape tests/test_obs.py pins for the in-process edge).
_PROM_LINE = re.compile(
    r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* ?.*"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9][0-9eE+.\-]*)$"
)


def _wait_healthy(client: HTTPClient, timeout_s: float) -> None:
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            if client.healthz().get("status") == "ok":
                return
        except Exception:  # noqa: BLE001 - server still booting
            pass
        if time.monotonic() > deadline:
            raise SystemExit(f"server not healthy after {timeout_s:.0f}s")
        time.sleep(0.5)


def _parse_args():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--url", required=True, help="base URL of a serve_cv --http server")
    ap.add_argument("--json", default=None, metavar="PATH", help="latency artifact path")
    ap.add_argument(
        "--n",
        type=int,
        default=96,
        help="samples (match the server's --n so warmed eval shapes cover this traffic)",
    )
    ap.add_argument("--p", type=int, default=256, help="features")
    ap.add_argument("--k", type=int, default=6, help="folds (match server --k)")
    ap.add_argument("--lam", type=float, default=1.0)
    ap.add_argument(
        "--perm",
        type=int,
        default=64,
        help="permutation draws (match server --perm buckets)",
    )
    ap.add_argument("--reps", type=int, default=16, help="warm latency samples")
    ap.add_argument("--boot-timeout", type=float, default=180.0)
    ap.add_argument(
        "--expect-warm",
        action="store_true",
        help="assert the warmed families serve first traffic with zero "
        "compiles (server must run --warmup with matching --n/--k/--perm)",
    )
    return ap.parse_args()


def main() -> int:
    args = _parse_args()
    client = HTTPClient(args.url)
    _wait_healthy(client, args.boot_timeout)
    print(f"[http_smoke] {args.url} healthy")

    # Local reference: the same dataset + workloads through the in-process
    # Client. Bit-identical across the process boundary is the contract.
    x, yc = synthetic.make_classification(
        jax.random.PRNGKey(7), args.n, args.p, num_classes=3, class_sep=2.0
    )
    y = jnp.where(yc % 2 == 0, -1.0, 1.0)
    folds = foldlib.kfold(args.n, args.k, seed=3)
    local = Client(CVEngine())
    local_handle = local.register(x, folds, args.lam)

    handle = client.register(
        np.asarray(x), (np.asarray(folds.te_idx), np.asarray(folds.tr_idx)), args.lam
    )
    assert handle.key == local_handle.key, "wire registration changed the fingerprint"

    models = jnp.stack([jnp.ones((3, 3)) - jnp.eye(3), jnp.eye(3) * 0.0 + 0.5])
    mc = Workload(kind="cv", dataset=handle, y=yc, estimator="multiclass", num_classes=3)
    warmed = [
        ("cv/binary", Workload(kind="cv", dataset=handle, y=y)),
        ("cv/ridge", Workload(kind="cv", dataset=handle, y=y, estimator="ridge")),
        ("cv/multiclass", mc),
        (
            "permutation",
            Workload(kind="permutation", dataset=handle, y=y, n_perm=args.perm, seed=11),
        ),
    ]
    cold = [
        (
            "rsa",
            Workload(
                kind="rsa",
                dataset=handle,
                y=yc,
                num_classes=3,
                model_rdms=models,
                n_perm=16,
                seed=5,
            ),
        ),
        ("tune", Workload(kind="tune", x=x, y=y)),
        ("grid", Workload(kind="grid", dataset=handle, y=y, xs=jnp.stack([x, x * 1.05]))),
    ]

    def swap(w, ds):
        d = w.to_dict()
        if isinstance(d.get("dataset"), dict) and "__handle__" in d["dataset"]:
            d["dataset"] = ds.to_dict()
        return Workload.from_dict(d)

    compiles0 = client.stats()["engine"]["compiles"]
    for name, w in warmed:
        assert_responses_equal(client.submit(w), local.submit(swap(w, local_handle)), label=name)
    warm_delta = client.stats()["engine"]["compiles"] - compiles0
    print(f"[http_smoke] warmed families conformant; first-traffic compiles: {warm_delta}")
    if args.expect_warm:
        assert warm_delta == 0, (
            f"--warmup did not cover first wire traffic ({warm_delta} compiles)"
        )

    for name, w in cold:
        assert_responses_equal(client.submit(w), local.submit(swap(w, local_handle)), label=name)
    print("[http_smoke] all five compute kinds bit-identical over the wire")

    # SSE chunks == monolithic null, draw for draw
    stream_w = warmed[3][1]
    events = list(client.stream(stream_w))
    mono = local.submit(swap(stream_w, local_handle))
    streamed = np.concatenate([np.asarray(ev.payload) for ev in events if ev.kind == "null"])
    np.testing.assert_array_equal(streamed, np.asarray(mono.null))
    print(f"[http_smoke] SSE stream conformant ({len(events)} events)")

    # warm replay: every kind again, zero compiles end to end
    before = client.stats()["engine"]["compiles"]
    t_submit = []
    for name, w in warmed + cold:
        t0 = time.perf_counter()
        client.submit(w)
        t_submit.append(time.perf_counter() - t0)
    list(client.stream(stream_w))
    replay_delta = client.stats()["engine"]["compiles"] - before
    assert replay_delta == 0, f"{replay_delta} compiles on warm wire replay"
    print("[http_smoke] warm replay: 0 post-warmup compiles")

    # mutable versioned datasets round-trip the wire: POST .../append
    # advances the version; GET /v1/datasets and the per-dataset stats
    # reflect version/n_appended; plan updates never recompile
    view0 = {d["handle"].key: d for d in client.datasets()}
    assert view0[handle.key]["version"] == 0
    assert view0[handle.key]["n_appended"] == 0
    before_update = client.stats()["engine"]["compiles"]
    x_new = np.asarray(
        jax.random.normal(jax.random.PRNGKey(13), (args.k, args.p)), dtype=np.float64
    )
    h1 = client.append(handle, x_new)
    assert (h1.version, h1.n_appended, h1.n) == (1, args.k, args.n + args.k), (
        f"append returned version={h1.version} n_appended={h1.n_appended} n={h1.n}"
    )
    view1 = {d["handle"].key: d for d in client.datasets()}
    assert view1[handle.key]["version"] == 0, "base version must stay registered"
    assert view1[h1.key]["version"] == 1 and view1[h1.key]["n_appended"] == args.k
    per = client.stats()["engine"]["per_dataset"]
    fp12 = str(h1.key[0])[:12]
    assert per[fp12]["version"] == 1 and per[fp12]["n_appended"] == args.k, (
        f"per_dataset stats missing the appended version: {per.get(fp12)}"
    )
    update_delta = client.stats()["engine"]["compiles"] - before_update
    assert update_delta == 0, f"{update_delta} compiles from a plan update"
    print(
        f"[http_smoke] versioned append conformant (v0 -> v{h1.version}, "
        f"n_appended={h1.n_appended}, 0 compiles)"
    )

    # /v1/metrics: exposition parses line by line, every stage histogram is
    # pre-declared, and compile_events is flat across scrape → submit → scrape
    text = client.metrics_text()
    for line in text.splitlines():
        if not line:
            continue
        assert _PROM_LINE.match(line), f"malformed exposition line: {line!r}"
    for stage in STAGES:
        needle = f'stage_latency_seconds_bucket{{stage="{stage}"'
        assert needle in text, f"stage histogram missing from /v1/metrics: {stage}"
    m = re.search(r"^compile_events (\d+)$", text, re.M)
    assert m, "compile_events missing from /v1/metrics"
    client.submit(warmed[0][1])
    m2 = re.search(r"^compile_events (\d+)$", client.metrics_text(), re.M)
    assert m2 and m2.group(1) == m.group(1), (
        f"compile_events moved on a warm scrape replay: {m.group(1)} -> "
        f"{m2.group(1) if m2 else 'missing'}"
    )
    trace_view = client.trace(n=8)
    assert {"enabled", "ring", "traces", "summary"} <= trace_view.keys()
    print(
        f"[http_smoke] /v1/metrics conformant ({len(text.splitlines())} lines, "
        f"compile_events={m.group(1)} flat); /v1/trace "
        f"{'enabled' if trace_view['enabled'] else 'disabled'}"
    )

    # latency rows (the artifact CI publishes next to bench-smoke)
    lat = []
    cv_w = warmed[0][1]
    for _ in range(args.reps):
        t0 = time.perf_counter()
        client.submit(cv_w)
        lat.append(time.perf_counter() - t0)
    pct = percentiles(lat, (50, 95))
    t0 = time.perf_counter()
    t_first = None
    for ev in client.stream(stream_w):
        if ev.kind == "null" and t_first is None:
            t_first = time.perf_counter() - t0

    def smoke_row(name, seconds, derived):
        return dict(section="http-smoke", **row(name, seconds, derived))

    rows = [
        smoke_row(
            f"http_smoke_submit_N{args.n}_P{args.p}",
            pct["p50"],
            f"p95={pct['p95'] * 1e3:.1f}ms over {args.reps} warm submits",
        ),
        smoke_row(
            f"http_smoke_mixed_kinds_{len(warmed) + len(cold)}req",
            float(np.median(t_submit)),
            "median per-workload submit across all five kinds",
        ),
        smoke_row(
            f"http_smoke_stream_first_chunk_T{args.perm}",
            t_first,
            "SSE time-to-first-null-chunk",
        ),
    ]
    for r in rows:
        print(f"[http_smoke] {r['name']},{r['us_per_call']:.1f},{r['derived']}")

    if args.json:
        meta = {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "url": args.url,
            "expect_warm": bool(args.expect_warm),
            "timestamp": datetime.datetime.now(datetime.timezone.utc).isoformat(timespec="seconds"),
        }
        with open(args.json, "w") as fh:
            json.dump({"meta": meta, "rows": rows}, fh, indent=2)
        print(f"[http_smoke] wrote {len(rows)} rows to {args.json}")
    print("[http_smoke] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
