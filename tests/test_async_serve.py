"""Tests for repro.serve.aio: concurrent ragged clients against the async
server, streamed permutation/RSA responses, warm-up's zero-recompile
guarantee, and plan pinning under cache pressure.

Like tests/test_serve.py, this suite exercises the *deprecated request
shims* on purpose — the async server must keep accepting them unchanged
while tests/test_workload.py pins their parity with the Workload path
(including async stream == sync stream event-for-event)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import rsa
from repro.core import fastcv, folds as foldlib
from repro.data import synthetic
from repro.serve import (
    AsyncEngineServer,
    CVEngine,
    DatasetSpec,
    EngineConfig,
    ProgressEvent,
    Workload,
    run_workloads,
)

N, P, K, LAM = 48, 96, 4, 1.0


@pytest.fixture(scope="module")
def problem():
    x, yc = synthetic.make_classification(
        jax.random.PRNGKey(0), N, P, num_classes=3, class_sep=2.0
    )
    y = jnp.where(yc % 2 == 0, -1.0, 1.0)
    f = foldlib.kfold(N, K, seed=1)
    return x, y, yc, f


def _spec(problem):
    x, _, _, f = problem
    return DatasetSpec(x, f, LAM)


def _mixed_requests(problem, n_perm=12):
    x, y, yc, f = problem
    spec = DatasetSpec(x, f, LAM)
    return [
        Workload(kind="cv", dataset=spec, y=y, estimator="binary"),
        Workload(kind="cv", dataset=spec, y=-y, estimator="binary"),
        Workload(kind="cv", dataset=spec, y=jnp.stack([y, -y, jnp.roll(y, 3)], axis=1),
                 estimator="binary"),
        Workload(kind="cv", dataset=spec, y=y, estimator="ridge"),
        Workload(kind="cv", dataset=spec, y=yc, estimator="multiclass", num_classes=3),
        Workload(kind="permutation", dataset=spec, y=y, n_perm=n_perm, seed=4),
        Workload(kind="tune", x=x, y=y),
    ]


# ---------------------------------------------------------------------------
# Concurrent submission: correctness per request, one plan, shared batches
# ---------------------------------------------------------------------------


def test_async_server_matches_sync(problem):
    requests = _mixed_requests(problem)
    sync = run_workloads(CVEngine(), requests)
    engine = CVEngine()

    async def main():
        async with AsyncEngineServer(engine, gather_window_ms=5.0) as server:
            return await asyncio.gather(*(server.submit(r) for r in requests))

    results = asyncio.run(main())
    for got, want in zip(results, sync):
        assert type(got) is type(want)
        if hasattr(want, "values"):
            np.testing.assert_allclose(
                np.asarray(got.values), np.asarray(want.values), rtol=1e-9, atol=1e-12
            )
        elif hasattr(want, "null"):
            np.testing.assert_allclose(
                np.asarray(got.null), np.asarray(want.null), rtol=1e-9, atol=1e-12
            )
    # every request shares the one dataset -> one plan build total
    assert engine.stats()["plans_built"] == 1


def test_async_ragged_concurrent_clients(problem):
    """8 clients with ragged mixed-task streams: per-request results must
    match the direct library answers, through shared coalesced batches."""
    x, y, yc, f = problem
    spec = DatasetSpec(x, f, LAM)
    engine = CVEngine()
    dv_direct, _ = fastcv.binary_cv(x, y, f, lam=LAM)

    async def client(server, cid):
        width = 1 + cid % 3
        cols = jnp.stack([jnp.roll(y, cid + j) for j in range(width)], axis=1)
        resp_b = await server.submit(
            Workload(kind="cv", dataset=spec, y=cols, estimator="binary")
        )
        resp_m = await server.submit(
            Workload(kind="cv", dataset=spec, y=yc, estimator="multiclass", num_classes=3)
        )
        return cid, cols, resp_b, resp_m

    async def main():
        async with AsyncEngineServer(engine, gather_window_ms=5.0) as server:
            out = await asyncio.gather(*(client(server, cid) for cid in range(8)))
            return out, server.requests_served, server.batches_served

    out, served, batches = asyncio.run(main())
    assert served == 16
    assert batches < served  # concurrency actually coalesced
    e_ref = CVEngine()
    _, plan = e_ref.plan(x, f, LAM)
    pred_ref = e_ref.eval_multiclass(plan, yc, 3)
    for cid, cols, resp_b, resp_m in out:
        assert resp_b.values.shape[-1] == cols.shape[1]
        want = e_ref.eval_binary(plan, cols)
        np.testing.assert_allclose(
            np.asarray(resp_b.values), np.asarray(want), rtol=1e-9, atol=1e-12
        )
        assert bool(jnp.all(resp_m.values == pred_ref))
    assert engine.stats()["plans_built"] == 1
    # client 0's first column is the unrolled y -> the direct library answer
    np.testing.assert_allclose(
        np.asarray(out[0][2].values[..., 0]), np.asarray(dv_direct), rtol=1e-9, atol=1e-12
    )


def test_async_server_propagates_errors(problem):
    engine = CVEngine()
    # Estimator names are validated eagerly at construction, so smuggle an
    # invalid one past __post_init__ to exercise serve-time propagation.
    bad = Workload(kind="cv", dataset=_spec(problem), y=problem[1])
    object.__setattr__(bad, "estimator", "nonsense")

    async def main():
        async with AsyncEngineServer(engine) as server:
            with pytest.raises(ValueError):
                await server.submit(bad)

    asyncio.run(main())


def test_async_server_rejects_after_stop(problem):
    engine = CVEngine()

    async def main():
        server = AsyncEngineServer(engine)
        await server.start()
        await server.stop()
        with pytest.raises(RuntimeError):
            await server.submit(Workload(kind="cv", dataset=_spec(problem), y=problem[1]))

    asyncio.run(main())


# ---------------------------------------------------------------------------
# Warm-up: compile_count stays flat under concurrent mixed traffic
# ---------------------------------------------------------------------------


def test_warmup_then_zero_recompiles_under_traffic(problem):
    x, y, yc, f = problem
    spec = DatasetSpec(x, f, LAM)
    engine = CVEngine()
    info = engine.warmup(
        spec,
        tasks=("binary", "ridge", "multiclass", "permutation"),
        buckets=(1, 2, 4, 8, 16),
        num_classes=3,
    )
    assert info["buckets"] == (1, 2, 4, 8, 16)
    warm = engine.compile_count()
    assert warm == info["compiles"]

    async def client(server, cid):
        await server.submit(
            Workload(kind="cv", dataset=spec, y=jnp.roll(y, cid), estimator="binary")
        )
        await server.submit(
            Workload(kind="cv", dataset=spec, y=yc, estimator="multiclass", num_classes=3)
        )
        await server.submit(
            Workload(kind="cv", dataset=spec, y=jnp.roll(y, cid + 1), estimator="ridge")
        )
        await server.submit(Workload(kind="permutation", dataset=spec, y=y, n_perm=14, seed=cid))

    async def main():
        async with AsyncEngineServer(engine, gather_window_ms=3.0) as server:
            await asyncio.gather(*(client(server, cid) for cid in range(8)))

    asyncio.run(main())
    assert engine.compile_count() == warm  # zero recompiles after warm-up
    assert engine.stats()["plans_built"] == 1  # warm-up built the only plan


def test_warmup_validates_arguments(problem):
    engine = CVEngine()
    with pytest.raises(ValueError):
        engine.warmup(_spec(problem), tasks=("nonsense",))
    with pytest.raises(ValueError):
        engine.warmup(_spec(problem), tasks=("multiclass",), num_classes=0)


# ---------------------------------------------------------------------------
# Streaming: permutation null chunks and RSA events
# ---------------------------------------------------------------------------


def test_stream_permutation_chunks_match_monolithic(problem):
    x, y, _, f = problem
    spec = DatasetSpec(x, f, LAM)
    engine = CVEngine()

    async def main():
        events = []
        async with AsyncEngineServer(engine, stream_chunk=8) as server:
            w = Workload(kind="permutation", dataset=spec, y=y, n_perm=20, seed=4)
            async for ev in server.stream(w):
                events.append(ev)
        return events

    events = asyncio.run(main())
    kinds = [ev.kind for ev in events]
    assert kinds[:2] == ["plan", "observed"]
    assert kinds[-1] == "done"
    null_events = [ev for ev in events if ev.kind == "null"]
    assert [ev.done for ev in null_events] == [8, 16, 20]
    assert all(isinstance(ev, ProgressEvent) and ev.total == 20 for ev in events)
    streamed_null = jnp.concatenate([ev.payload for ev in null_events])
    final = events[-1].payload
    assert final.null.shape == (20,)
    np.testing.assert_array_equal(np.asarray(streamed_null), np.asarray(final.null))
    # identical draws as the monolithic path (prefix-stable permutations)
    ref = CVEngine()
    _, plan = ref.plan(x, f, LAM)
    mono = ref.permutation_binary(plan, y, 20, jax.random.PRNGKey(4))
    np.testing.assert_allclose(np.asarray(final.null), np.asarray(mono.null), rtol=1e-9, atol=1e-12)
    assert float(final.p) == pytest.approx(float(mono.p), abs=1e-12)


def test_stream_multiclass_permutation(problem):
    x, _, yc, f = problem
    spec = DatasetSpec(x, f, LAM)
    engine = CVEngine()
    req = Workload(kind="permutation", dataset=spec, y=yc, n_perm=10, seed=2,
                   estimator="multiclass", num_classes=3)

    async def main():
        async with AsyncEngineServer(engine, stream_chunk=4) as server:
            return [ev async for ev in server.stream(req)]

    events = asyncio.run(main())
    final = events[-1].payload
    ref = CVEngine()
    _, plan = ref.plan(x, f, LAM)
    mono = ref.permutation_multiclass(plan, yc, 10, jax.random.PRNGKey(2), num_classes=3)
    np.testing.assert_allclose(np.asarray(final.null), np.asarray(mono.null), rtol=1e-9, atol=1e-12)


def test_stream_rsa_events(problem):
    x, _, yc, f = problem
    c = 3
    spec = DatasetSpec(x, foldlib.stratified_kfold(yc, K, seed=0), LAM)
    models = jnp.stack([rsa.ring_rdm(c), rsa.ring_rdm(c) * 0.5 + 0.1])
    engine = CVEngine()
    req = Workload(kind="rsa", dataset=spec, y=yc, num_classes=c,
                   model_rdms=models, n_perm=10, seed=3)

    async def main():
        async with AsyncEngineServer(engine, stream_chunk=4) as server:
            return [ev async for ev in server.stream(req)]

    events = asyncio.run(main())
    kinds = [ev.kind for ev in events]
    assert kinds[0] == "plan" and kinds[1] == "rdm" and kinds[2] == "scores"
    assert kinds[-1] == "done"
    final = events[-1].payload
    (sync,) = run_workloads(CVEngine(), [req])
    np.testing.assert_allclose(np.asarray(final.rdm), np.asarray(sync.rdm), rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(
        np.asarray(final.model_scores), np.asarray(sync.model_scores), rtol=1e-9, atol=1e-12
    )
    np.testing.assert_allclose(np.asarray(final.null), np.asarray(sync.null), rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(np.asarray(final.p), np.asarray(sync.p), rtol=1e-9, atol=1e-12)


def test_stream_non_streamable_degenerates_to_done(problem):
    x, y, _, f = problem
    engine = CVEngine()
    req = Workload(kind="cv", dataset=DatasetSpec(x, f, LAM), y=y, estimator="binary")

    async def main():
        async with AsyncEngineServer(engine) as server:
            return [ev async for ev in server.stream(req)]

    events = asyncio.run(main())
    assert [ev.kind for ev in events] == ["done"]
    dv, _ = fastcv.binary_cv(x, y, f, lam=LAM)
    np.testing.assert_allclose(
        np.asarray(events[0].payload.values), np.asarray(dv), rtol=1e-9, atol=1e-12
    )


# ---------------------------------------------------------------------------
# Pinning: pinned plans survive cache pressure end to end
# ---------------------------------------------------------------------------


def test_pinned_plan_survives_cache_pressure(problem):
    x, _, _, f = problem
    _, probe = CVEngine().plan(x, f, LAM)
    engine = CVEngine(EngineConfig(cache_bytes=2 * probe.nbytes + 1))
    spec = DatasetSpec(x, f, LAM)
    info = engine.warmup(spec, tasks=("binary",), buckets=(1,), pin=True)
    assert info["pinned"]
    pinned_key = info["plan_key"]
    for lam in (0.5, 2.0, 4.0, 8.0):  # pressure: 4 more plans through a 2-plan budget
        engine.plan(x, f, lam)
    assert pinned_key in engine.cache  # pinned plan never evicted
    stats = engine.stats()
    assert stats["pinned"] == 1
    assert stats["pinned_bytes"] == probe.nbytes
    assert stats["evictions"] >= 2
    # pinned bytes are excluded from pressure: unpinned usage fits the budget
    assert stats["bytes_in_use"] - stats["pinned_bytes"] <= stats["byte_budget"]
    # unpinning re-subjects the plan to LRU pressure
    assert engine.unpin(pinned_key)
    assert engine.stats()["pinned"] == 0
