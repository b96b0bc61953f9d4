"""Tests for the tracer's profiler side: ``repro.<stage>`` annotations on
the ``jax.profiler`` clock, the ``executor_wait`` stage of the async
server's engine thread, the HTTP trace from arrival to encoded response,
and interpreter GC pauses as the ``gc`` stage.

Tracing off must stay invisible: no ``TraceAnnotation`` is constructed and
no ``gc.callbacks`` hook is registered.
"""

import gc
import statistics
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import folds as foldlib
from repro.data import synthetic
from repro.serve import STAGES, Client, CVEngine, EngineConfig, Workload
from repro.serve.http import EdgeThread, HTTPClient
from repro.serve.trace import Tracer

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))  # the benchmark's trace reduction lives in bench/

N, P, K, LAM = 48, 64, 4, 1.0


@pytest.fixture(scope="module")
def problem():
    x, yc = synthetic.make_classification(
        jax.random.PRNGKey(0), N, P, num_classes=3, class_sep=2.0
    )
    y = jnp.where(yc % 2 == 0, -1.0, 1.0)
    return x, y, foldlib.kfold(N, K, seed=1)


@pytest.fixture()
def engine():
    eng = CVEngine(EngineConfig(cache_bytes=64 << 20))
    yield eng
    eng.disable_tracing()


@pytest.fixture()
def counted_annotations(monkeypatch):
    """Names of every ``jax.profiler.TraceAnnotation`` made while active."""
    names = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            names.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    return names


def _workloads(handle, y):
    return [
        Workload(kind="cv", dataset=handle, y=y, estimator="binary"),
        Workload(kind="permutation", dataset=handle, y=y, n_perm=16, seed=3),
    ]


def test_spans_reach_the_profiler_trace(problem, engine, tmp_path):
    from bench import trace_reduce

    x, y, f = problem
    client = Client(engine)
    handle = client.register(x, f, LAM)
    ws = _workloads(handle, y)
    client.gather(ws)  # warm: plans built, programs compiled
    engine.enable_tracing()
    opts = jax.profiler.ProfileOptions()  # as the benchmark's traced run records
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            for w in ws:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    client.submit(w)
    finally:
        jax.profiler.stop_trace()
    red = trace_reduce.reduce_trace(trace_reduce.find_xplane(tmp_path))
    labelled = {label.partition(" / ")[2] for label, _ in red["idle_gaps"]}
    stages = {label[len("repro."):] for label in labelled if label.startswith("repro.")}
    assert stages, f"no idle gap carries a repro.<stage> label: {sorted(labelled)}"
    assert stages <= set(STAGES)
    assert all(label.startswith("bench.submit / ") for label, _ in red["idle_gaps"]
               if "repro." in label)


def test_disabled_tracing_annotates_nothing_and_hooks_no_gc(
    problem, engine, counted_annotations
):
    x, y, f = problem
    gc.collect()  # tracers left enabled by earlier tests unhook as they are collected
    callbacks = list(gc.callbacks)
    client = Client(engine)
    handle = client.register(x, f, LAM)
    ws = _workloads(handle, y)
    client.gather(ws)
    with EdgeThread(engine) as edge, HTTPClient(edge.url) as wire:
        wire.submit(ws[0])
    gc.collect()
    assert counted_annotations == []
    assert gc.callbacks == callbacks

    engine.enable_tracing()
    assert len(gc.callbacks) == len(callbacks) + 1
    client.gather(ws)
    assert any(name.startswith("repro.") for name in counted_annotations)
    engine.disable_tracing()
    assert gc.callbacks == callbacks


def test_gc_hook_leaves_with_its_tracer():
    gc.collect()
    callbacks = list(gc.callbacks)
    tracer = Tracer(enabled=True)
    assert len(gc.callbacks) == len(callbacks) + 1
    del tracer
    gc.collect()
    assert gc.callbacks == callbacks


def test_gc_pause_is_a_stage_outside_request_timings(problem, engine, counted_annotations):
    x, y, f = problem
    hist = engine.metrics.get("stage_latency_seconds")
    engine.enable_tracing()
    before = hist.snapshot(stage="gc")["count"]
    gc.collect()
    after = hist.snapshot(stage="gc")
    assert after["count"] >= before + 1 and after["sum"] > 0.0
    assert "repro.gc" in counted_annotations
    client = Client(engine)
    resp = client.submit(Workload(kind="cv", dataset=client.register(x, f, LAM), y=y))
    gc.collect()
    assert "gc" not in resp.timings
    assert all("gc" not in t["timings"] for t in engine.tracer.last())


def _coverage_gap(trace: dict) -> float:
    return abs(sum(trace["timings"].values()) - trace["duration_s"])


def test_http_trace_runs_from_arrival_to_encoded_response(problem, engine):
    x, y, f = problem
    with EdgeThread(engine) as edge, HTTPClient(edge.url) as client:
        handle = client.register(np.asarray(x), f, LAM)
        w = Workload(kind="cv", dataset=handle, y=y, estimator="binary")
        client.submit(w)  # warm
        engine.enable_tracing(ring=16)
        responses = [client.submit(w) for _ in range(5)]
        traces = engine.tracer.last(5)
    for resp in responses:
        # the wire timings are what was known when the response was built
        assert {"decode", "batch_wait", "executor_wait", "eval", "encode"} <= set(resp.timings)
    for tr in traces:
        names = [s["name"] for s in tr["spans"]]
        decode = tr["spans"][0]
        assert decode["name"] == "decode"
        assert [c["name"] for c in decode["children"]] == ["executor_wait"]
        assert decode["children"][0]["duration_s"] <= decode["duration_s"]
        # two top-level waits: the run_workloads hop and the wire-encode hop
        assert names.count("executor_wait") == 2
        # response assembly, then the wire encode, which ends the trace
        assert names.count("encode") == 2 and names[-1] == "encode"
        last = tr["spans"][-1]
        assert last["start_s"] + last["duration_s"] <= tr["duration_s"]
    dur = statistics.median(t["duration_s"] for t in traces)
    gap = statistics.median(_coverage_gap(t) for t in traces)
    assert gap <= max(0.05 * dur, 1e-3), (gap, dur)
    hist = engine.metrics.get("stage_latency_seconds")
    # one encode observation per request: assembly and wire encode summed
    assert hist.snapshot(stage="encode")["count"] == len(traces)
    assert hist.snapshot(stage="executor_wait")["count"] == len(traces)


def test_multi_workload_body_members_share_decode_and_encode(problem, engine):
    x, y, f = problem
    with EdgeThread(engine) as edge, HTTPClient(edge.url) as client:
        handle = client.register(np.asarray(x), f, LAM)
        ws = [Workload(kind="cv", dataset=handle, y=jnp.roll(y, i)) for i in range(3)]
        client.gather(ws)  # warm
        engine.enable_tracing(ring=16)
        client.gather(ws)
        traces = engine.tracer.last(3)
    assert len(traces) == 3
    firsts = [t["spans"][0] for t in traces]
    lasts = [t["spans"][-1] for t in traces]
    assert {s["name"] for s in firsts} == {"decode"} and {s["name"] for s in lasts} == {"encode"}
    # one decode and one wire encode for the body, attributed to each member in full
    assert len({s["duration_s"] for s in firsts}) == 1
    assert len({s["duration_s"] for s in lasts}) == 1
