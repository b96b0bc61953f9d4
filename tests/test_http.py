"""Wire-conformance suite for the HTTP/SSE edge (repro.serve.http).

The contract under test: the HTTP edge is a *transport*, not a second
implementation — every byte of JSON decodes to the exact Workload the
in-process Client would construct, so results are bit-identical, SSE
chunks are the same chunks stream_workload yields (prefix-stable,
identical draws to the monolithic path), and a warm engine serves wire
traffic with zero extra compiles. Error paths (malformed JSON, unknown
schema, unknown/evicted handles, oversized bodies, mid-stream
disconnects) return structured JSON errors and leave the engine's
stats()/compile_count untouched. Per-workload failures surface as
result-or-error entries without aborting sibling workloads — on the
in-process transports and over the wire alike.
"""

import json
import socket
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import rsa
from repro.core import folds as foldlib
from repro.data import synthetic
from repro.serve import (
    Client,
    CVEngine,
    DatasetHandle,
    EdgeThread,
    HTTPClient,
    Workload,
    WireError,
    estimators,
)
from repro.serve.http import assert_responses_equal

N, P, K, LAM = 48, 96, 4, 1.0


@pytest.fixture(scope="module")
def problem():
    x, yc = synthetic.make_classification(
        jax.random.PRNGKey(0), N, P, num_classes=3, class_sep=2.0
    )
    y = jnp.where(yc % 2 == 0, -1.0, 1.0)
    f = foldlib.kfold(N, K, seed=1)
    return x, y, yc, f


def _register_over_wire(hclient, problem):
    x, _, _, f = problem
    return hclient.register(
        np.asarray(x), (np.asarray(f.te_idx), np.asarray(f.tr_idx)), LAM
    )


def _workload_matrix(problem, dataset):
    """All five kinds; cv covers every registered estimator."""
    x, y, yc, _ = problem
    q = jnp.stack([y, -y, jnp.roll(y, 5)], axis=1)
    models = jnp.stack([rsa.ring_rdm(3), rsa.ring_rdm(3) * 0.5 + 0.1])
    return [
        ("cv/binary", Workload(kind="cv", dataset=dataset, y=y)),
        ("cv/ridge", Workload(kind="cv", dataset=dataset, y=y, estimator="ridge")),
        ("cv/multiclass", Workload(kind="cv", dataset=dataset, y=yc,
                                   estimator="multiclass", num_classes=3)),
        ("cv/ridge_multi", Workload(kind="cv", dataset=dataset, y=q,
                                    estimator="ridge_multi")),
        ("permutation/binary", Workload(kind="permutation", dataset=dataset, y=y,
                                        n_perm=12, seed=4)),
        ("permutation/multiclass", Workload(kind="permutation", dataset=dataset, y=yc,
                                            estimator="multiclass", num_classes=3,
                                            n_perm=10, seed=2)),
        ("rsa/binary+models", Workload(kind="rsa", dataset=dataset, y=yc, num_classes=3,
                                       model_rdms=models, n_perm=8, seed=2)),
        ("rsa/multiclass", Workload(kind="rsa", dataset=dataset, y=yc, num_classes=3,
                                    contrast="multiclass")),
        ("tune", Workload(kind="tune", x=x, y=y)),
        ("grid", Workload(kind="grid", dataset=dataset, y=y,
                          xs=jnp.stack([x, x * 1.05]))),
    ]


# the one equality contract, shared with the live-server smoke harness
_assert_responses_equal = assert_responses_equal


def _recv_response(s, raw=b""):
    """Read one HTTP response (headers + Content-Length body) off a socket."""
    while True:
        head_part, sep, body_part = raw.partition(b"\r\n\r\n")
        if sep:
            length = 0
            for hline in head_part.split(b"\r\n")[1:]:
                if hline.lower().startswith(b"content-length:"):
                    length = int(hline.split(b":")[1])
            if len(body_part) >= length:
                return raw
        b = s.recv(65536)
        if not b:
            return raw
        raw += b


def _raw_request(edge, payload: bytes, path="/v1/workloads", extra_headers=""):
    """One hand-rolled POST; returns (status, parsed-or-None body)."""
    with socket.create_connection(("127.0.0.1", edge.port), timeout=60) as s:
        head = (f"POST {path} HTTP/1.1\r\nHost: t\r\n"
                f"Content-Length: {len(payload)}\r\n{extra_headers}\r\n")
        s.sendall(head.encode() + payload)
        raw = _recv_response(s)
    status = int(raw.split(b" ", 2)[1])
    body = raw.partition(b"\r\n\r\n")[2]
    try:
        return status, json.loads(body.decode())
    except ValueError:
        return status, None


# ---------------------------------------------------------------------------
# The differential harness: HTTP == in-process, bit for bit, compile-flat
# ---------------------------------------------------------------------------


def test_wire_conformance_bit_identical_and_compile_flat(problem):
    """Every workload kind and every registered estimator: the HTTP result
    is bit-identical to the in-process Client result, and a second wire
    pass adds zero compiles and zero plan builds to the engine."""
    x, _, _, f = problem
    assert {"binary", "ridge", "multiclass", "ridge_multi"} <= set(estimators())

    ref_client = Client(CVEngine())
    ref_handle = ref_client.register(x, f, LAM)
    refs = [ref_client.submit(w) for _, w in _workload_matrix(problem, ref_handle)]

    with EdgeThread() as edge, HTTPClient(edge.url) as hc:
        handle = _register_over_wire(hc, problem)
        assert handle.key == ref_handle.key  # same bytes -> same fingerprint
        ws = _workload_matrix(problem, handle)
        got_cold = [hc.submit(w) for _, w in ws]
        for (name, _), a, b in zip(ws, got_cold, refs):
            _assert_responses_equal(a, b)

        warm_compiles = edge.engine.compile_count()
        warm_plans = edge.engine.stats()["plans_built"]
        got_warm = [hc.submit(w) for _, w in ws]
        assert edge.engine.compile_count() == warm_compiles
        assert edge.engine.stats()["plans_built"] == warm_plans
        for (name, _), a, b in zip(ws, got_warm, refs):
            _assert_responses_equal(a, b)


def test_inline_dataset_spec_over_the_wire(problem):
    """Workloads may also ship the feature matrix inline (DatasetSpec)."""
    from repro.serve import DatasetSpec

    x, y, _, f = problem
    ref = Client(CVEngine()).submit(Workload(kind="cv", dataset=DatasetSpec(x, f, LAM), y=y))
    with EdgeThread() as edge, HTTPClient(edge.url) as hc:
        got = hc.submit(Workload(kind="cv", dataset=DatasetSpec(x, f, LAM), y=y))
        _assert_responses_equal(got, ref)


def test_http_batch_gather_matches_in_process(problem):
    """A whole batch through POST /v1/workloads coalesces in the async
    gather window; per-request results match the library answers (allclose
    at 1e-9, matching the repo's concurrent-coalescing precedent)."""
    x, y, yc, f = problem
    ref_client = Client(CVEngine())
    ref_handle = ref_client.register(x, f, LAM)
    batch_of = lambda h: [
        Workload(kind="cv", dataset=h, y=jnp.roll(y, i)) for i in range(3)
    ] + [
        Workload(kind="cv", dataset=h, y=yc, estimator="multiclass", num_classes=3),
        Workload(kind="permutation", dataset=h, y=y, n_perm=12, seed=7),
    ]
    refs = [ref_client.submit(w) for w in batch_of(ref_handle)]
    with EdgeThread() as edge, HTTPClient(edge.url) as hc:
        handle = _register_over_wire(hc, problem)
        got = hc.gather(batch_of(handle))
        assert edge.edge.server.batches_served < len(got)  # actually coalesced
        for a, b in zip(got, refs):
            assert type(a) is type(b)
            for field in ("values", "null"):
                va, vb = getattr(a, field, None), getattr(b, field, None)
                if va is not None:
                    np.testing.assert_allclose(
                        np.asarray(va), np.asarray(vb), rtol=1e-9, atol=1e-12
                    )


# ---------------------------------------------------------------------------
# SSE streaming: same chunks as stream_workload, ragged concurrent clients
# ---------------------------------------------------------------------------


def test_sse_chunks_bit_identical_ragged_concurrent(problem, monkeypatch):
    """Streamed permutation-null and RSA chunks over concurrent ragged HTTP
    clients are byte-identical to the monolithic responses, prefix by
    prefix — and the chunks really are evaluated chunk-wise on the engine
    (call-counting monkeypatch, as in test_workload's mesh test)."""
    x, y, yc, f = problem
    chunk = 8
    perms = (12, 20, 28)
    models = jnp.stack([rsa.ring_rdm(3), rsa.ring_rdm(3) * 0.5 + 0.1])

    # monolithic references from a fresh in-process engine
    ref_client = Client(CVEngine())
    ref_handle = ref_client.register(x, f, LAM)
    mono = {
        t: ref_client.submit(
            Workload(kind="permutation", dataset=ref_handle, y=y, n_perm=t, seed=t)
        )
        for t in perms
    }
    mono_rsa = ref_client.submit(
        Workload(kind="rsa", dataset=ref_handle, y=yc, num_classes=3,
                 model_rdms=models, n_perm=16, seed=3)
    )

    calls = {"n": 0}
    real = CVEngine.null_binary

    def counting(self, *args, **kwargs):
        calls["n"] += 1
        return real(self, *args, **kwargs)

    monkeypatch.setattr(CVEngine, "null_binary", counting)

    with EdgeThread(stream_chunk=chunk) as edge:
        hc0 = HTTPClient(edge.url)
        handle = _register_over_wire(hc0, problem)
        hc0.close()

        results = {}

        def one_client(t):
            with HTTPClient(edge.url) as hc:
                w = Workload(kind="permutation", dataset=handle, y=y, n_perm=t, seed=t)
                results[t] = list(hc.stream(w))

        def rsa_client():
            with HTTPClient(edge.url) as hc:
                w = Workload(kind="rsa", dataset=handle, y=yc, num_classes=3,
                             model_rdms=models, n_perm=16, seed=3)
                results["rsa"] = list(hc.stream(w))

        threads = [threading.Thread(target=one_client, args=(t,)) for t in perms]
        threads.append(threading.Thread(target=rsa_client))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)

        # chunk-wise evaluation actually happened (>= one call per chunk)
        assert calls["n"] >= sum(-(-t // chunk) for t in perms)

        for t in perms:
            events = results[t]
            assert [e.kind for e in events[:2]] == ["plan", "observed"]
            lo = 0
            for ev in events:
                if ev.kind != "null":
                    continue
                block = np.asarray(ev.payload)
                np.testing.assert_array_equal(  # prefix-stable chunks
                    block, np.asarray(mono[t].null)[lo : lo + block.shape[0]]
                )
                lo += block.shape[0]
            final = events[-1].payload
            np.testing.assert_array_equal(np.asarray(final.null), np.asarray(mono[t].null))
            np.testing.assert_array_equal(np.asarray(final.p), np.asarray(mono[t].p))

        rsa_events = results["rsa"]
        kinds = [e.kind for e in rsa_events]
        assert kinds[:3] == ["plan", "rdm", "scores"] and kinds[-1] == "done"
        lo = 0
        for ev in rsa_events:
            if ev.kind != "null":
                continue
            block = np.asarray(ev.payload)
            np.testing.assert_array_equal(
                block, np.asarray(mono_rsa.null)[:, lo : lo + block.shape[1]]
            )
            lo += block.shape[1]
        _assert_responses_equal(rsa_events[-1].payload, mono_rsa)


# ---------------------------------------------------------------------------
# Error paths: structured JSON, engine untouched
# ---------------------------------------------------------------------------


def _engine_fingerprint(engine):
    s = engine.stats()
    return (s["compiles"], s["plans_built"], s["labels_evaluated"])


def test_error_paths_are_structured_and_leave_engine_untouched(problem):
    x, y, _, f = problem
    with EdgeThread(max_body_bytes=1 << 20) as edge, HTTPClient(edge.url) as hc:
        handle = _register_over_wire(hc, problem)
        hc.submit(Workload(kind="cv", dataset=handle, y=y))  # prime/warm
        before = _engine_fingerprint(edge.engine)

        # malformed JSON
        status, body = _raw_request(edge, b"{this is not json")
        assert status == 400 and body["error"]["type"] == "bad_json"

        # unknown schema version (the eager from_dict validation message)
        d = Workload(kind="cv", dataset=handle, y=y).to_dict()
        d["schema"] = 99
        status, body = _raw_request(edge, json.dumps(d).encode())
        entry = body["results"][0]
        assert status == 200 and not entry["ok"]
        assert entry["error"]["status"] == 400
        assert "unsupported workload schema" in entry["error"]["message"]
        # ...and on the stream route, which rejects before any SSE bytes
        status, body = _raw_request(edge, json.dumps(d).encode(),
                                    path="/v1/workloads/stream")
        assert status == 400 and body["error"]["type"] == "validation"
        assert "unsupported workload schema" in body["error"]["message"]

        # eager Workload validation message travels verbatim
        bad = Workload(kind="cv", dataset=handle, y=y).to_dict()
        bad["y"]["__array__"] = [2.0] * N  # not ±1-coded
        status, body = _raw_request(edge, json.dumps(bad).encode())
        entry = body["results"][0]
        assert status == 200 and not entry["ok"]
        assert "±1" in entry["error"]["message"]
        assert entry["error"]["status"] == 400

        # unknown handle
        fake = DatasetHandle(key=("bogus", "te", "tr", 1.0, "dual", True),
                             n=N, p=P, lam=LAM)
        with pytest.raises(WireError, match="not registered") as ei:
            hc.submit(Workload(kind="cv", dataset=fake, y=y))
        assert ei.value.status == 404 and ei.value.etype == "unknown_dataset"

        # evicted + deregistered handle
        x2 = x * 1.25
        h2 = hc.register(np.asarray(x2), (np.asarray(f.te_idx), np.asarray(f.tr_idx)), LAM)
        edge.engine.evict(h2, deregister=True)
        with pytest.raises(WireError, match="not registered") as ei:
            hc.submit(Workload(kind="cv", dataset=h2, y=y))
        assert ei.value.status == 404

        # oversized body: rejected from Content-Length alone — the edge
        # answers without reading a single body byte (none is ever sent)
        with socket.create_connection(("127.0.0.1", edge.port), timeout=60) as s:
            s.sendall(
                (f"POST /v1/workloads HTTP/1.1\r\nHost: t\r\n"
                 f"Content-Length: {(1 << 20) + 1}\r\n\r\n").encode()
            )
            raw = _recv_response(s)
        status = int(raw.split(b" ", 2)[1])
        body = json.loads(raw.partition(b"\r\n\r\n")[2].decode())
        assert status == 413 and body["error"]["type"] == "oversized"

        # chunked request bodies: explicit 411, not a desynced parser
        with socket.create_connection(("127.0.0.1", edge.port), timeout=60) as s:
            s.sendall(
                b"POST /v1/workloads HTTP/1.1\r\nHost: t\r\n"
                b"Transfer-Encoding: chunked\r\n\r\n5\r\nhello\r\n0\r\n\r\n"
            )
            raw = _recv_response(s)
        assert raw.split(b" ", 2)[1] == b"411"
        err = json.loads(raw.partition(b"\r\n\r\n")[2].decode())
        assert err["error"]["type"] == "length_required"

        # unknown routes / methods
        status, body = _raw_request(edge, b"{}", path="/v1/nonsense")
        assert status == 404 and body["error"]["type"] == "not_found"

        assert _engine_fingerprint(edge.engine) == before
        # the edge counted its errors, and stays fully serviceable
        assert hc.stats()["edge"]["http_errors"] >= 5
        assert hc.healthz() == {"status": "ok"}


def test_expect_100_continue_handshake(problem):
    """curl adds `Expect: 100-continue` to >1KB POSTs (any real dataset
    registration) and stalls ~1s unless the edge answers the interim 100."""
    x, y, _, f = problem
    with EdgeThread() as edge, HTTPClient(edge.url) as hc:
        handle = _register_over_wire(hc, problem)
        body = json.dumps(Workload(kind="cv", dataset=handle, y=y).to_dict()).encode()
        with socket.create_connection(("127.0.0.1", edge.port), timeout=60) as s:
            s.sendall(
                (f"POST /v1/workloads HTTP/1.1\r\nHost: t\r\n"
                 f"Expect: 100-continue\r\nContent-Length: {len(body)}\r\n\r\n").encode()
            )
            interim = s.recv(1024)
            assert interim.startswith(b"HTTP/1.1 100 Continue")
            s.sendall(body)
            raw = _recv_response(s, interim.partition(b"\r\n\r\n")[2])
        assert raw.split(b" ", 2)[1] == b"200"
        out = json.loads(raw.partition(b"\r\n\r\n")[2].decode())
        assert out["results"][0]["ok"] is True


def test_client_disconnect_mid_stream_keeps_serving(problem):
    x, y, _, f = problem
    with EdgeThread(stream_chunk=8) as edge, HTTPClient(edge.url) as hc:
        handle = _register_over_wire(hc, problem)
        w = Workload(kind="permutation", dataset=handle, y=y, n_perm=40, seed=1)
        full = list(hc.stream(w))  # prime: all chunk programs compiled
        compiles = edge.engine.compile_count()

        # a client that reads the headers plus a little and hangs up
        body = json.dumps(w.to_dict()).encode()
        with socket.create_connection(("127.0.0.1", edge.port), timeout=60) as s:
            s.sendall(
                (f"POST /v1/workloads/stream HTTP/1.1\r\nHost: t\r\n"
                 f"Content-Length: {len(body)}\r\n\r\n").encode() + body
            )
            s.recv(1024)

        # the edge survives: same stream again, bit-identical, no recompiles
        again = list(hc.stream(w))
        assert [e.kind for e in again] == [e.kind for e in full]
        np.testing.assert_array_equal(
            np.asarray(again[-1].payload.null), np.asarray(full[-1].payload.null)
        )
        assert edge.engine.compile_count() == compiles
        assert hc.healthz() == {"status": "ok"}


# ---------------------------------------------------------------------------
# Per-workload failures never abort siblings (both transports)
# ---------------------------------------------------------------------------


def _bad_handle():
    return DatasetHandle(key=("bogus", "te", "tr", 1.0, "dual", True), n=N, p=P, lam=LAM)


@pytest.mark.parametrize("transport", ["sync", "async"])
def test_gather_surfaces_per_entry_errors_in_process(problem, transport):
    x, y, yc, f = problem
    engine = CVEngine()
    client = Client(engine)
    handle = client.register(x, f, LAM)
    good1 = Workload(kind="cv", dataset=handle, y=y)
    bad = Workload(kind="cv", dataset=_bad_handle(), y=y)
    good2 = Workload(kind="cv", dataset=handle, y=yc, estimator="multiclass", num_classes=3)
    ref1, ref2 = client.submit(good1), client.submit(good2)

    if transport == "async":
        import asyncio

        async def drive():
            async with Client(engine, transport="async") as ac:
                return await ac.gather([good1, bad, good2], return_errors=True)

        out = asyncio.run(drive())
    else:
        out = client.gather([good1, bad, good2], return_errors=True)
    assert isinstance(out[1], KeyError)
    _assert_responses_equal(out[0], ref1)
    _assert_responses_equal(out[2], ref2)

    # default semantics unchanged: raise on the first failure
    with pytest.raises(KeyError, match="not registered"):
        client.gather([good1, bad, good2])


def test_http_gather_surfaces_per_entry_errors(problem):
    x, y, _, f = problem
    ref_client = Client(CVEngine())
    ref_handle = ref_client.register(x, f, LAM)
    ref = ref_client.submit(Workload(kind="cv", dataset=ref_handle, y=y))
    with EdgeThread() as edge, HTTPClient(edge.url) as hc:
        handle = _register_over_wire(hc, problem)
        good = Workload(kind="cv", dataset=handle, y=y)
        bad = Workload(kind="cv", dataset=_bad_handle(), y=y)
        out = hc.gather([good, bad, good], return_errors=True)
        assert isinstance(out[1], WireError)
        assert out[1].status == 404 and "not registered" in str(out[1])
        # the two good siblings coalesced into one padded eval (width 2),
        # so compare at the repo's concurrent-coalescing tolerance
        for got in (out[0], out[2]):
            assert type(got) is type(ref)
            np.testing.assert_allclose(
                np.asarray(got.values), np.asarray(ref.values), rtol=1e-9, atol=1e-12
            )
        with pytest.raises(WireError, match="not registered"):
            hc.gather([good, bad])


# ---------------------------------------------------------------------------
# Ops surface: registration, introspection, stats
# ---------------------------------------------------------------------------


def test_register_is_idempotent_and_introspectable_over_wire(problem):
    x, y, _, f = problem
    with EdgeThread() as edge, HTTPClient(edge.url) as hc:
        h1 = _register_over_wire(hc, problem)
        h2 = _register_over_wire(hc, problem)
        assert h1 == h2 and h1.n == N and h1.p == P
        (info,) = hc.datasets()
        assert info["handle"] == h1 and info["resident"] is False
        hc.submit(Workload(kind="cv", dataset=h1, y=y))
        (info,) = hc.datasets()
        assert info["resident"] is True and info["served"] == 1

        s = hc.stats()
        assert s["engine"]["datasets_registered"] == 1
        assert s["server"]["requests_served"] == 1
        assert s["edge"]["http_requests"] >= 4
