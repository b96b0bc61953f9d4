"""Tests for the observability layer (PR 6): the metrics registry
(repro.serve.obs), request-scoped tracing (repro.serve.trace), the
engine/server/edge instrumentation, and the /v1/metrics + /v1/trace
exposition routes.

Two invariants matter more than any individual counter:

* **Zero overhead when disabled** — tracing off (the default) must leave
  responses without ``timings``, add no compiles, and keep the wire
  payload byte-identical to the pre-observability schema.
* **stats() schema preserved** — the registry is a *view* over existing
  counters (cache stats, compile_count); ``engine.stats()`` keeps its
  key set, with ``per_dataset`` as the only addition.
"""

import asyncio
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import folds as foldlib
from repro.data import synthetic
from repro.serve import (
    STAGES,
    Client,
    CVEngine,
    DatasetSpec,
    EngineConfig,
    MetricsRegistry,
    Workload,
)
from repro.serve.http import EdgeThread, HTTPClient
from repro.serve.trace import Trace, Tracer, attach_trace, trace_of

N, P, K, LAM = 48, 64, 4, 1.0


@pytest.fixture(scope="module")
def problem():
    x, yc = synthetic.make_classification(
        jax.random.PRNGKey(0), N, P, num_classes=3, class_sep=2.0
    )
    y = jnp.where(yc % 2 == 0, -1.0, 1.0)
    f = foldlib.kfold(N, K, seed=1)
    return x, y, yc, f


@pytest.fixture()
def engine():
    return CVEngine(EngineConfig(cache_bytes=64 << 20))


def _kinds_workloads(problem, client):
    """One workload per kind (cv, permutation, rsa, tune, grid)."""
    x, y, yc, f = problem
    handle = client.register(x, f, LAM)
    return [
        Workload(kind="cv", dataset=handle, y=y, estimator="binary"),
        Workload(kind="permutation", dataset=handle, y=y, n_perm=8, seed=3),
        Workload(
            kind="rsa",
            dataset=handle,
            y=yc,
            num_classes=3,
            model_rdms=jnp.ones((1, 3, 3)),
            n_perm=8,
            seed=2,
        ),
        Workload(kind="tune", x=x, y=y),
        Workload(kind="grid", dataset=DatasetSpec(None, f, LAM), y=y, xs=jnp.stack([x])),
    ]


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------


def test_counter_semantics():
    reg = MetricsRegistry()
    c = reg.counter("reqs", "requests", labels=("kind",))
    c.inc(kind="cv")
    c.inc(2, kind="cv")
    c.inc(kind="rsa")
    assert c.value(kind="cv") == 3
    assert c.value(kind="rsa") == 1
    assert c.value(kind="tune") == 0
    with pytest.raises(ValueError):
        c.inc(-1, kind="cv")
    reg.inc("reqs", kind="cv")  # by-name dispatch
    assert c.value(kind="cv") == 4
    with pytest.raises(KeyError):
        reg.inc("no_such_metric")
    g = reg.gauge("g", "a gauge")
    g.set(5)
    with pytest.raises(TypeError):
        reg.inc("g")  # wrong metric kind


def test_gauge_callback_semantics():
    reg = MetricsRegistry()
    state = {"v": 7}
    g = reg.gauge("live", "callback-backed", fn=lambda: state["v"])
    assert g.value() == 7
    state["v"] = 11
    assert g.value() == 11  # lazy: source of truth stays canonical
    assert "live 11" in reg.render_prometheus()
    with pytest.raises(ValueError):
        g.set(3)  # callback gauges cannot be set directly


def test_histogram_observe_snapshot_and_cumulative_render():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency", buckets=(0.1, 1.0, 10.0), labels=("stage",))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):  # last one overflows every edge
        h.observe(v, stage="eval")
    snap = h.snapshot(stage="eval")
    assert snap["count"] == 5
    assert snap["buckets"] == [1, 2, 1]  # per-bucket, non-cumulative
    assert snap["sum"] == pytest.approx(56.05)
    text = "\n".join(h.render())
    # exposition is cumulative-le, with +Inf == count
    assert 'lat_bucket{stage="eval",le="0.1"} 1' in text
    assert 'lat_bucket{stage="eval",le="1"} 3' in text
    assert 'lat_bucket{stage="eval",le="10"} 4' in text
    assert 'lat_bucket{stage="eval",le="+Inf"} 5' in text
    assert 'lat_count{stage="eval"} 5' in text


def test_registration_idempotent_but_type_mismatch_raises():
    reg = MetricsRegistry()
    c1 = reg.counter("x", "first")
    c2 = reg.counter("x", "second registration returns the first")
    assert c1 is c2
    with pytest.raises(ValueError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.histogram("x", buckets=(1.0,))


def test_cardinality_cap_folds_into_other():
    reg = MetricsRegistry(max_series_per_metric=4)
    c = reg.counter("labelled", "capped", labels=("who",))
    for i in range(10):
        c.inc(who=f"client-{i}")
    assert len(c._series) <= 5  # 4 real + 1 overflow
    assert reg.dropped_series == 6
    assert c.value(who="_other") == 6
    text = reg.render_prometheus()
    assert 'labelled{who="_other"} 6' in text
    assert "obs_dropped_series 6" in text


_PROM_LINE = re.compile(
    r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* ?.*"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9][0-9eE+.\-]*)$"
)


def test_prometheus_text_parses_line_by_line(engine):
    text = engine.metrics.render_prometheus()
    assert text.endswith("\n")
    for line in text.rstrip("\n").split("\n"):
        assert _PROM_LINE.match(line), f"malformed exposition line: {line!r}"


def test_stage_histograms_pre_declared(engine):
    """A fresh engine's exposition lists every stage series before any
    traffic — CI greps these to prove instrumentation is wired."""
    text = engine.metrics.render_prometheus()
    for stage in STAGES:
        assert f'stage_latency_seconds_bucket{{stage="{stage}"' in text, stage
    assert "\ncompile_events 0" in text
    assert "requests_total 0" in text  # unlabelled zero placeholder


# ---------------------------------------------------------------------------
# stats() schema is preserved (+ the handle-scoped view)
# ---------------------------------------------------------------------------

_GOLDEN_STATS_KEYS = {
    "hits",
    "misses",
    "evictions",
    "oversized",
    "pinned",
    "pinned_bytes",
    "bytes_in_use",
    "byte_budget",
    "plans_built",
    "plans_updated",
    "labels_evaluated",
    "compiles",
    "datasets_registered",
    "rdm_hits",
    "rdm_entries",
    "store_hits",
    "store_misses",
    "store_writes",
    "store_bytes",
    "per_dataset",
}


def test_stats_schema_golden(problem, engine):
    x, y, _, f = problem
    handle = engine.register(x, f, LAM)
    Client(engine).submit(Workload(kind="cv", dataset=handle, y=y, estimator="binary"))
    s = engine.stats()
    assert set(s) == _GOLDEN_STATS_KEYS
    per = s["per_dataset"]
    assert len(per) == 1
    (rec,) = per.values()
    assert set(rec) == {"n", "p", "version", "n_appended", "served",
                        "plan_bytes", "resident", "pinned", "last_used"}
    assert rec["version"] == 0 and rec["n_appended"] == 0
    assert rec["n"] == N and rec["p"] == P
    assert rec["served"] == 1
    assert rec["resident"] and rec["plan_bytes"] > 0
    assert rec["last_used"] > 0.0


# ---------------------------------------------------------------------------
# Tracing: span mechanics
# ---------------------------------------------------------------------------


def test_span_nesting_and_top_level_timings():
    tr = Trace(kind="cv")
    with tr.span("eval"):
        with tr.span("null_chunk"):
            pass
    with tr.span("encode"):
        pass
    assert [s.name for s in tr.spans] == ["eval", "encode"]
    assert [c.name for c in tr.spans[0].children] == ["null_chunk"]
    t = tr.timings()
    assert set(t) == {"eval", "encode"}  # children never double-count
    d = tr.to_dict()
    assert d["spans"][0]["children"][0]["name"] == "null_chunk"


def test_tracer_disabled_hooks_are_noops():
    tracer = Tracer()
    assert tracer.trace() is None
    with tracer.activate(None):
        with tracer.span("eval"):
            pass
    assert tracer.current() is None
    assert tracer.last() == []
    assert tracer.summary() == {}


def test_attach_trace_and_finished_reuse_guard():
    tracer = Tracer(enabled=True)
    w = Workload(kind="tune", x=jnp.ones((8, 4)), y=jnp.ones(8))
    tr = tracer.trace()
    attach_trace(w, tr)
    assert trace_of(w) is tr
    tracer.finish(tr)
    assert trace_of(w) is None  # finished traces are never reused
    assert len(tracer.last()) == 1


def test_ring_is_bounded():
    tracer = Tracer(enabled=True, ring=4)
    for _ in range(10):
        tracer.finish(tracer.trace())
    assert tracer.ring_size == 4
    assert len(tracer.last(100)) == 4


# ---------------------------------------------------------------------------
# End-to-end: disabled == invisible, enabled == full span trees
# ---------------------------------------------------------------------------


def test_disabled_tracing_no_timings_no_extra_compiles(problem, engine):
    ws = _kinds_workloads(problem, Client(engine))
    client = Client(engine)
    first = client.gather(ws)
    compiles = engine.compile_count()
    second = client.gather(ws)
    assert engine.compile_count() == compiles
    for resp in first + second:
        assert resp.timings is None
    assert engine.tracer.last() == []


def test_enabled_tracing_all_kinds_sync(problem, engine):
    client = Client(engine)
    ws = _kinds_workloads(problem, client)
    client.gather(ws)  # warm: plans built, programs compiled
    compiles = engine.compile_count()
    engine.enable_tracing(ring=32)
    responses = client.gather(ws)
    assert engine.compile_count() == compiles  # tracing adds no compiles
    for w, resp in zip(ws, responses):
        assert resp.timings, f"no timings for kind={w.kind}"
        assert set(resp.timings) <= set(STAGES)
        assert "validate" in resp.timings and "encode" in resp.timings
        assert ("eval" in resp.timings) or ("null_chunk" in resp.timings)
    kinds = {t["kind"] for t in engine.tracer.last()}
    assert kinds == {"cv", "permutation", "rsa", "tune", "grid"}
    # requests_total counted per kind
    reqs = engine.metrics.get("requests_total")
    assert reqs.value(kind="cv", estimator="binary") >= 1
    assert reqs.value(kind="tune", estimator="") >= 1
    # per-stage histogram fed by finished traces
    h = engine.metrics.get("stage_latency_seconds")
    assert h.snapshot(stage="eval")["count"] >= 1
    assert h.snapshot(stage="encode")["count"] >= len(ws)


def test_async_transport_batch_wait_and_stage_sum(problem, engine):
    x, y, _, f = problem
    handle = engine.register(x, f, LAM)
    w = Workload(kind="cv", dataset=handle, y=y, estimator="binary")

    async def go():
        async with Client(engine, transport="async") as client:
            await client.submit(w)  # warm
            engine.enable_tracing()
            return await client.submit(w)

    resp = asyncio.run(go())
    assert "batch_wait" in resp.timings
    (trace,) = engine.tracer.last(1)
    stage_sum = sum(trace["timings"].values())
    dur = trace["duration_s"]
    # warm path: the instrumented stages account for the request end-to-end
    assert abs(stage_sum - dur) <= max(0.05 * dur, 1e-3), (stage_sum, dur)
    occ = engine.metrics.get("gather_window_occupancy")
    assert occ.snapshot()["count"] >= 2


def test_async_transport_timings(problem, engine):
    x, y, _, f = problem
    handle = engine.register(x, f, LAM)
    w = Workload(kind="cv", dataset=handle, y=y, estimator="binary")

    async def go():
        async with Client(engine, transport="async") as client:
            await client.submit(w)  # warm
            engine.enable_tracing()
            return await client.submit(w)

    resp = asyncio.run(go())
    assert resp.timings and "batch_wait" in resp.timings and "eval" in resp.timings


def test_streamed_workload_carries_timings(problem, engine):
    x, y, _, f = problem
    handle = engine.register(x, f, LAM)
    engine.enable_tracing()
    events = list(
        Client(engine).stream(
            Workload(kind="permutation", dataset=handle, y=y, n_perm=16, seed=1)
        )
    )
    done = events[-1]
    assert done.kind == "done"
    assert done.payload.timings and "null_chunk" in done.payload.timings


def test_batch_coalesced_size_observed(problem, engine):
    x, y, _, f = problem
    handle = engine.register(x, f, LAM)
    ws = [
        Workload(kind="cv", dataset=handle, y=jnp.roll(y, i), estimator="binary")
        for i in range(3)
    ]
    Client(engine).gather(ws)
    h = engine.metrics.get("batch_coalesced_size")
    snap = h.snapshot()
    assert snap["count"] >= 1
    assert snap["sum"] >= 3  # the three queries coalesced into one batch


# ---------------------------------------------------------------------------
# The HTTP edge: timings on the wire, /v1/metrics, /v1/trace
# ---------------------------------------------------------------------------


def test_http_edge_metrics_trace_and_wire_timings(problem):
    x, y, _, f = problem
    engine = CVEngine(EngineConfig(cache_bytes=64 << 20))
    with EdgeThread(engine) as edge, HTTPClient(edge.url) as client:
        handle = client.register(np.asarray(x), f, LAM)
        w = Workload(kind="cv", dataset=handle, y=y, estimator="binary")
        r0 = client.submit(w)
        assert r0.timings is None  # tracing off: wire schema untouched
        engine.enable_tracing(ring=16)
        resp = client.submit(w)
        assert resp.timings and "decode" in resp.timings and "eval" in resp.timings
        assert "batch_wait" in resp.timings

        text = client.metrics_text()
        for line in text.rstrip("\n").split("\n"):
            assert _PROM_LINE.match(line), f"malformed exposition line: {line!r}"
        assert re.search(r"^compile_events \d+$", text, re.M)
        for stage in STAGES:
            assert f'stage_latency_seconds_bucket{{stage="{stage}"' in text

        payload = client.trace(8)
        assert payload["enabled"] is True
        assert payload["ring"] == 16
        assert payload["traces"], "ring should hold the traced request"
        tree = payload["traces"][0]
        assert tree["kind"] == "cv"
        span_names = {s["name"] for s in tree["spans"]}
        assert {"decode", "validate", "eval", "encode"} <= span_names
        assert payload["summary"]["eval"]["count"] >= 1
