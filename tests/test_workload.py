"""Tests for the One-API surface: Workload schema + validation, estimator
registry, dataset handles, core parity across the sync and async transports,
compile-count flatness between spec- and handle-addressed traffic, the
0.3 removal of the legacy request shims, RDM memoisation, traffic
record/replay, and mesh-aware streamed nulls."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fastcv, folds as foldlib, multiclass, multidim, regression, tuning
from repro.data import synthetic
from repro.serve import (
    Client,
    CVEngine,
    CVResponse,
    DatasetHandle,
    DatasetSpec,
    EngineConfig,
    GridResponse,
    LeastSquaresSpec,
    TrafficLog,
    Workload,
    as_workload,
    estimators,
    register_estimator,
    run_workloads,
    stream_workload,
)
from repro.serve import workload as workload_mod

N, P, K, LAM = 48, 96, 4, 1.0


@pytest.fixture(scope="module")
def problem():
    x, yc = synthetic.make_classification(
        jax.random.PRNGKey(0), N, P, num_classes=3, class_sep=2.0
    )
    y = jnp.where(yc % 2 == 0, -1.0, 1.0)
    f = foldlib.kfold(N, K, seed=1)
    return x, y, yc, f


def _equiv_workloads(problem, dataset, n_perm=12):
    x, y, yc, _ = problem
    return [
        Workload(kind="cv", dataset=dataset, y=y, estimator="binary"),
        Workload(kind="cv", dataset=dataset, y=y, estimator="ridge"),
        Workload(kind="cv", dataset=dataset, y=yc, estimator="multiclass", num_classes=3),
        Workload(kind="permutation", dataset=dataset, y=y, n_perm=n_perm, seed=4),
        Workload(kind="rsa", dataset=dataset, y=yc, num_classes=3,
                 model_rdms=jnp.ones((1, 3, 3)), n_perm=8, seed=2),
        Workload(kind="tune", x=x, y=y),
    ]


def _assert_responses_equal(got, want, exact=True):
    assert type(got) is type(want)
    for field in ("values", "null", "rdm", "model_scores", "p", "score", "accuracies"):
        a, b = getattr(got, field, None), getattr(want, field, None)
        assert (a is None) == (b is None)
        if a is None:
            continue
        if exact:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-12)
    if hasattr(want, "result"):
        assert float(got.result.best_lambda) == float(want.result.best_lambda)


# ---------------------------------------------------------------------------
# 0.3: the deprecated request shims are gone
# ---------------------------------------------------------------------------


def test_removed_shims_raise_importerror_with_migration_pointer():
    # the package namespace does not advertise the removed request classes
    import repro.serve as serve_pkg
    for name in ("CVRequest", "PermutationRequest", "RSARequest", "TuneRequest"):
        with pytest.raises(AttributeError):
            getattr(serve_pkg, name)


def test_as_workload_rejects_foreign_objects_with_migration_pointer(problem):
    x, y, _, f = problem

    class FakeLegacyRequest:
        pass

    with pytest.raises(TypeError, match="README"):
        as_workload(FakeLegacyRequest())


@pytest.mark.parametrize("addressing", ["handle", "spec"])
def test_async_transport_matches_sync(problem, addressing):
    """The same traffic is bit-identical through the sync and async
    transports (sequential submission => identical padded shapes), whether
    it names a registered handle or carries an inline DatasetSpec."""
    x, _, _, f = problem
    results = {}
    for transport in ("sync", "async"):
        engine = CVEngine()
        dataset = engine.register(x, f, LAM) if addressing == "handle" else DatasetSpec(x, f, LAM)
        ws = _equiv_workloads(problem, dataset)
        if transport == "async":

            async def drive(ws=ws, engine=engine):
                async with Client(engine, transport="async") as client:
                    return [await client.submit(w) for w in ws]

            results[transport] = asyncio.run(drive())
        else:
            client = Client(engine)
            results[transport] = [client.submit(w) for w in ws]
    for got, want in zip(results["async"], results["sync"]):
        _assert_responses_equal(got, want, exact=True)


def test_compile_count_flat_across_spec_and_handle_traffic(problem):
    """Spec-addressed then handle-addressed versions of the same traffic
    must not retrace anything: one program family, not two."""
    x, _, _, f = problem
    engine = CVEngine()
    run_workloads(engine, _equiv_workloads(problem, DatasetSpec(x, f, LAM)))
    warm = engine.compile_count()
    run_workloads(engine, _equiv_workloads(problem, DatasetSpec(x, f, LAM)))
    handle = engine.register(x, f, LAM)
    run_workloads(engine, _equiv_workloads(problem, handle))
    assert engine.compile_count() == warm
    assert engine.stats()["plans_built"] == 1


# ---------------------------------------------------------------------------
# core/ convenience entry points == Workload path
# ---------------------------------------------------------------------------


def test_core_binary_cv_parity(problem):
    x, y, _, f = problem
    dv, y_te = fastcv.binary_cv(x, y, f, lam=LAM)
    resp = Client().submit(Workload(kind="cv", dataset=DatasetSpec(x, f, LAM), y=y))
    np.testing.assert_array_equal(np.asarray(resp.values), np.asarray(dv))
    np.testing.assert_array_equal(np.asarray(resp.y_te), np.asarray(y_te))


def test_core_analytical_cv_ridge_parity(problem):
    x, y, _, f = problem
    preds, _ = regression.analytical_cv(x, y, f, lam=LAM)
    resp = Client().submit(
        Workload(kind="cv", dataset=DatasetSpec(x, f, LAM), y=y, estimator="ridge")
    )
    np.testing.assert_array_equal(np.asarray(resp.values), np.asarray(preds))


def test_core_analytical_cv_multiclass_parity(problem):
    x, _, yc, f = problem
    preds, _ = multiclass.analytical_cv_multiclass(x, yc, f, 3, LAM)
    resp = Client().submit(
        Workload(kind="cv", dataset=DatasetSpec(x, f, LAM), y=yc,
                 estimator="multiclass", num_classes=3)
    )
    np.testing.assert_array_equal(np.asarray(resp.values), np.asarray(preds))


def test_core_tune_ridge_parity(problem):
    x, y, _, _ = problem
    direct = tuning.tune_ridge(x, y)
    resp = Client().submit(Workload(kind="tune", x=x, y=y))
    assert float(resp.result.best_lambda) == float(direct.best_lambda)
    np.testing.assert_array_equal(np.asarray(resp.result.scores), np.asarray(direct.scores))


def test_core_cv_grid_parity(problem):
    x, y, _, f = problem
    xs = jnp.stack([x, x * 1.05, jnp.roll(x, 1, axis=0)])
    direct = multidim.cv_grid(xs, y, f, LAM)
    resp = Client().submit(
        Workload(kind="grid", dataset=DatasetSpec(None, f, LAM), y=y, xs=xs)
    )
    assert isinstance(resp, GridResponse)
    np.testing.assert_array_equal(np.asarray(resp.accuracies), np.asarray(direct))


# ---------------------------------------------------------------------------
# Estimator registry: new least-squares models are registrations
# ---------------------------------------------------------------------------


def test_ridge_multi_registration(problem):
    """Multi-target ridge is served via registration alone — and shares the
    ridge evaluator's compiled programs (eval_key), so zero extra compiles."""
    x, y, _, f = problem
    engine = CVEngine()
    client = Client(engine)
    data = client.register(x, f, LAM)
    q = jnp.stack([y, -y, jnp.roll(y, 5)], axis=1)  # (N, 3) targets
    _, plan = engine.resolve(data)
    ref = engine.eval_ridge(plan, q)
    warm = engine.compile_count()
    resp = client.submit(Workload(kind="cv", dataset=data, y=q, estimator="ridge_multi"))
    assert engine.compile_count() == warm  # shared eval_key="ridge"
    np.testing.assert_array_equal(np.asarray(resp.values), np.asarray(ref))
    # variance-weighted multi-target R², not MSE
    y_te = q[plan.te_idx]
    v = np.asarray(ref).reshape(-1, 3)
    t = np.asarray(y_te).reshape(-1, 3)
    r2 = np.mean(1 - ((t - v) ** 2).sum(0) / ((t - t.mean(0)) ** 2).sum(0))
    assert float(resp.score) == pytest.approx(r2, rel=1e-9)
    with pytest.raises(ValueError, match="needs \\(N, Q\\)"):
        Workload(kind="cv", dataset=DatasetSpec(x, f, LAM), y=y, estimator="ridge_multi")


def test_third_party_estimator_registration(problem):
    """A model family added by registration alone: demeaned-target ridge.
    No engine, driver, or transport changes — and no new compiled programs
    (it shares the Eq. 14 evaluator via eval_key)."""
    x, y, _, f = problem
    name = "ridge_demeaned"

    def encode(yv, dtype, opts):
        yb = jnp.asarray(yv)
        squeeze = yb.ndim == 1
        yb = (yb[:, None] if squeeze else yb).astype(dtype)
        return yb - jnp.mean(yb, axis=0, keepdims=True), squeeze

    register_estimator(LeastSquaresSpec(
        name=name,
        layout="columns",
        make_eval=lambda opts, donate, fused: fastcv.make_eval_cv(donate=donate, fused=fused),
        encode=encode,
        score=lambda values, y_te, opts: jnp.mean((values - y_te) ** 2),
        eval_key="ridge",
    ))
    try:
        assert name in estimators()
        with pytest.raises(ValueError, match="already registered"):
            register_estimator(LeastSquaresSpec(
                name=name, layout="columns",
                make_eval=lambda opts, donate, fused: fastcv.make_eval_cv(donate=donate, fused=fused),
            ))
        engine = CVEngine()
        client = Client(engine)
        data = client.register(x, f, LAM)
        client.submit(Workload(kind="cv", dataset=data, y=y, estimator="ridge"))
        warm = engine.compile_count()
        resp = client.submit(Workload(kind="cv", dataset=data, y=y, estimator=name))
        assert engine.compile_count() == warm
        _, plan = engine.resolve(data)
        ref = engine.eval_ridge(plan, y - jnp.mean(y))
        np.testing.assert_array_equal(np.asarray(resp.values), np.asarray(ref))
    finally:
        del workload_mod._ESTIMATORS[name]


# ---------------------------------------------------------------------------
# Schema: eager validation + versioned round-trip
# ---------------------------------------------------------------------------


def test_validation_rejects_malformed_workloads(problem):
    x, y, yc, f = problem
    spec = DatasetSpec(x, f, LAM)
    with pytest.raises(ValueError, match="unknown workload kind"):
        Workload(kind="nonsense", dataset=spec, y=y)
    with pytest.raises(ValueError, match="unknown estimator"):
        Workload(kind="cv", dataset=spec, y=y, estimator="nonsense")
    with pytest.raises(ValueError, match="±1"):
        Workload(kind="cv", dataset=spec, y=y * 2.0)
    with pytest.raises(ValueError, match="lie in \\[0, 3\\)"):
        Workload(kind="cv", dataset=spec, y=yc + 5, estimator="multiclass", num_classes=3)
    with pytest.raises(ValueError, match="n_perm > 0"):
        Workload(kind="permutation", dataset=spec, y=y, n_perm=0)
    with pytest.raises(ValueError, match="single \\(N,\\) target"):
        Workload(kind="permutation", dataset=spec, y=jnp.stack([y, -y], 1), n_perm=4)
    with pytest.raises(ValueError, match="metric"):
        Workload(kind="permutation", dataset=spec, y=y, n_perm=4, metric="nonsense")
    with pytest.raises(ValueError, match="num_classes >= 2"):
        Workload(kind="rsa", dataset=spec, y=yc, num_classes=0)
    with pytest.raises(ValueError, match="\\(M, C, C\\)"):
        Workload(kind="rsa", dataset=spec, y=yc, num_classes=3,
                 model_rdms=jnp.ones((2, 4, 4)))
    with pytest.raises(ValueError, match="comparison"):
        Workload(kind="rsa", dataset=spec, y=yc, num_classes=3, comparison="nonsense")
    with pytest.raises(ValueError, match="need a dataset"):
        Workload(kind="cv", y=y)
    with pytest.raises(ValueError, match="criterion"):
        Workload(kind="tune", x=x, y=y, criterion="nonsense")
    with pytest.raises(ValueError, match="\\(Q, N, P\\)"):
        Workload(kind="grid", dataset=spec, y=y, xs=x)


def test_workload_roundtrip_dict(problem):
    """to_dict/from_dict is versioned and result-preserving."""
    x, y, yc, f = problem
    spec = DatasetSpec(x, f, LAM)
    for w in (
        Workload(kind="cv", dataset=spec, y=y),
        Workload(kind="permutation", dataset=spec, y=y, n_perm=6, seed=3),
        Workload(kind="rsa", dataset=spec, y=yc, num_classes=3,
                 model_rdms=jnp.ones((1, 3, 3)), n_perm=4),
        Workload(kind="tune", x=x, y=y),
    ):
        d = w.to_dict()
        assert d["schema"] == 2
        back = Workload.from_dict(d)
        (a,) = run_workloads(CVEngine(), [w])
        (b,) = run_workloads(CVEngine(), [back])
        _assert_responses_equal(b, a, exact=True)
    with pytest.raises(ValueError, match="schema version"):
        Workload.from_dict({"schema": 99, "kind": "cv"})


def test_workload_roundtrip_preserves_handles(problem):
    x, y, _, f = problem
    engine = CVEngine()
    handle = engine.register(x, f, LAM)
    w = Workload(kind="cv", dataset=handle, y=y)
    back = Workload.from_dict(w.to_dict())
    assert isinstance(back.dataset, DatasetHandle)
    assert back.dataset.key == handle.key
    (a,) = run_workloads(engine, [w])
    (b,) = run_workloads(engine, [back])  # resolves through the same registration
    np.testing.assert_array_equal(np.asarray(a.values), np.asarray(b.values))


# ---------------------------------------------------------------------------
# Dataset registry: handles, introspection, handle-scoped ops
# ---------------------------------------------------------------------------


def test_register_is_idempotent_and_introspectable(problem):
    x, y, _, f = problem
    engine = CVEngine()
    h1 = engine.register(x, f, LAM)
    h2 = engine.register(x, f, LAM)
    assert h1 == h2
    assert h1.n == N and h1.p == P
    (info,) = engine.datasets()
    assert info["resident"] is False and info["served"] == 0
    run_workloads(engine, [Workload(kind="cv", dataset=h1, y=y)])
    (info,) = engine.datasets()
    assert info["resident"] is True and info["served"] == 1 and info["nbytes"] > 0


def test_handle_pin_warmup_evict(problem):
    x, y, _, f = problem
    engine = CVEngine()
    h = engine.register(x, f, LAM)
    info = engine.warmup(h, tasks=("binary",), buckets=(1,), pin=True)
    assert info["pinned"]
    assert engine.datasets()[0]["pinned"] is True
    assert engine.unpin(h)
    assert engine.evict(h)
    assert engine.datasets()[0]["resident"] is False
    # a handle workload transparently rebuilds the evicted plan
    built = engine.plans_built
    (resp,) = run_workloads(engine, [Workload(kind="cv", dataset=h, y=y)])
    assert isinstance(resp, CVResponse)
    assert engine.plans_built == built + 1
    engine.evict(h, deregister=True)
    with pytest.raises(KeyError, match="not registered"):
        run_workloads(engine, [Workload(kind="cv", dataset=h, y=y)])


def test_unregistered_handle_fails_clearly(problem):
    x, y, _, f = problem
    other = CVEngine()
    h = other.register(x, f, LAM)
    with pytest.raises(KeyError, match="not registered"):
        run_workloads(CVEngine(), [Workload(kind="cv", dataset=h, y=y)])


# ---------------------------------------------------------------------------
# RDM memoisation
# ---------------------------------------------------------------------------


def test_rdm_memoisation_skips_fold_solves(problem):
    x, _, yc, f = problem
    engine = CVEngine()
    client = Client(engine)
    data = client.register(x, foldlib.stratified_kfold(yc, K, seed=0), LAM)
    models = jnp.ones((2, 3, 3))
    w = Workload(kind="rsa", dataset=data, y=yc, num_classes=3,
                 model_rdms=models, n_perm=8, seed=1)
    r1 = client.submit(w)
    labels_after_first = engine.labels_evaluated
    assert engine.stats()["rdm_hits"] == 0
    r2 = client.submit(w)
    assert engine.stats()["rdm_hits"] == 1
    # the empirical RDM came from the memo: no further fold solves
    assert engine.labels_evaluated == labels_after_first
    np.testing.assert_array_equal(np.asarray(r1.rdm), np.asarray(r2.rdm))
    np.testing.assert_array_equal(np.asarray(r1.model_scores), np.asarray(r2.model_scores))
    # different labels -> different fingerprint -> miss
    client.submit(Workload(kind="rsa", dataset=data, y=(yc + 1) % 3, num_classes=3))
    assert engine.stats()["rdm_hits"] == 1
    assert engine.stats()["rdm_entries"] == 2


def test_rdm_memo_stable_across_plan_variants(problem):
    """The memo must hit even when the same workload is later served from
    the cached *superset* (train-block) plan instead of the train-free one."""
    x, y, yc, f = problem
    engine = CVEngine()
    spec = DatasetSpec(x, foldlib.stratified_kfold(yc, K, seed=0), LAM)
    w = Workload(kind="rsa", dataset=spec, y=yc, num_classes=3, adjust_bias=False)
    run_workloads(engine, [w])  # builds the with_train_block=False plan
    run_workloads(engine, [Workload(kind="cv", dataset=spec, y=y)])  # superset plan now resident
    run_workloads(engine, [w])  # resolves via the superset key; must still hit
    assert engine.stats()["rdm_hits"] == 1
    assert engine.stats()["rdm_entries"] == 1


def test_rdm_memo_streaming_and_batch_share_entries(problem):
    x, _, yc, f = problem
    engine = CVEngine()
    spec = DatasetSpec(x, foldlib.stratified_kfold(yc, K, seed=0), LAM)
    w = Workload(kind="rsa", dataset=spec, y=yc, num_classes=3)
    (batch,) = run_workloads(engine, [w])
    events = list(stream_workload(engine, w))
    assert engine.stats()["rdm_hits"] == 1  # the stream reused the memo
    np.testing.assert_array_equal(
        np.asarray(events[-1].payload.rdm), np.asarray(batch.rdm)
    )


# ---------------------------------------------------------------------------
# Traffic record / replay
# ---------------------------------------------------------------------------


def test_traffic_record_replay_roundtrip(tmp_path, problem):
    x, y, yc, f = problem
    log = TrafficLog()
    client = Client(record=log)
    data = client.register(x, f, LAM)
    client.submit(Workload(kind="cv", dataset=data, y=y))
    client.submit(Workload(kind="cv", dataset=data, y=yc,
                           estimator="multiclass", num_classes=3))
    client.submit(Workload(kind="permutation", dataset=data, y=y, n_perm=12, seed=0))
    client.submit(Workload(kind="tune", x=x, y=y))  # no plan -> not recorded
    assert len(log) == 3
    path = tmp_path / "traffic.json"
    log.save(path)
    loaded = TrafficLog.load(path)
    assert loaded.entries() == log.entries()

    # replay on a fresh engine: the recorded traffic then serves with zero
    # compiles and zero plan builds
    engine = CVEngine()
    h = engine.register(x, f, LAM)
    loaded.replay(engine, h, pin=True)
    warm = engine.compile_count()
    plans = engine.stats()["plans_built"]
    run_workloads(engine, [
        Workload(kind="cv", dataset=h, y=y),
        Workload(kind="cv", dataset=h, y=yc, estimator="multiclass", num_classes=3),
        Workload(kind="permutation", dataset=h, y=y, n_perm=12, seed=0),
    ])
    assert engine.compile_count() == warm
    assert engine.stats()["plans_built"] == plans
    assert engine.stats()["pinned"] == 1


def test_traffic_log_records_static_options(problem):
    """adjust_bias (a static jit option) and the confusion-contrast
    multiclass path must survive record -> replay."""
    x, y, yc, f = problem
    log = TrafficLog()
    client = Client(record=log)
    data = client.register(x, foldlib.stratified_kfold(yc, K, seed=0), LAM)
    client.submit(Workload(kind="cv", dataset=data, y=y, adjust_bias=False))
    client.submit(Workload(kind="rsa", dataset=data, y=yc, num_classes=3,
                           contrast="multiclass"))
    entries = log.entries()
    assert any(e["task"] == "binary" and e["adjust_bias"] is False for e in entries)
    assert any(e["task"] == "multiclass" for e in entries)  # confusion eval path
    engine = CVEngine()
    h = engine.register(x, foldlib.stratified_kfold(yc, K, seed=0), LAM)
    log.replay(engine, h)
    warm = engine.compile_count()
    run_workloads(engine, [
        Workload(kind="cv", dataset=h, y=y, adjust_bias=False),
        Workload(kind="rsa", dataset=h, y=yc, num_classes=3, contrast="multiclass"),
    ])
    assert engine.compile_count() == warm


def test_traffic_log_records_stream_chunk_bucket(problem):
    x, y, _, f = problem
    log = TrafficLog()
    client = Client(record=log, stream_chunk=8)
    data = client.register(x, f, LAM)
    list(client.stream(Workload(kind="permutation", dataset=data, y=y, n_perm=20, seed=0)))
    buckets = sorted(e["bucket"] for e in log.entries())
    assert buckets == [8, 32]  # the chunk program AND the monolithic bucket


def test_permutation_labels_evaluated_counts_requested_draws(problem):
    x, y, _, f = problem
    engine = CVEngine()
    _, plan = engine.plan(x, f, LAM)
    before = engine.labels_evaluated
    engine.permutation_binary(plan, y, 20, jax.random.PRNGKey(0))
    assert engine.labels_evaluated - before == 20  # requested draws, not bucket 32


def test_traffic_log_rejects_unknown_schema():
    with pytest.raises(ValueError, match="schema"):
        TrafficLog.from_json('{"schema": 42, "entries": []}')


# ---------------------------------------------------------------------------
# Streaming: sync generator + mesh-aware chunks
# ---------------------------------------------------------------------------


def test_sync_stream_matches_monolithic(problem):
    x, y, _, f = problem
    engine = CVEngine()
    spec = DatasetSpec(x, f, LAM)
    w = Workload(kind="permutation", dataset=spec, y=y, n_perm=20, seed=4)
    events = list(Client(engine, stream_chunk=8).stream(w))
    kinds = [ev.kind for ev in events]
    assert kinds[:2] == ["plan", "observed"] and kinds[-1] == "done"
    streamed = jnp.concatenate([ev.payload for ev in events if ev.kind == "null"])
    final = events[-1].payload
    np.testing.assert_array_equal(np.asarray(streamed), np.asarray(final.null))
    ref = CVEngine()
    _, plan = ref.plan(x, f, LAM)
    mono = ref.permutation_binary(plan, y, 20, jax.random.PRNGKey(4))
    np.testing.assert_allclose(np.asarray(final.null), np.asarray(mono.null),
                               rtol=1e-9, atol=1e-12)


def test_mesh_engine_streams_sharded_null_chunks(problem, monkeypatch):
    """ROADMAP gap: streamed permutation chunks must route through the
    mesh null program (sharded_null_from_plan inside one jit) on a
    mesh-configured engine, with draws identical to the monolithic (and
    local) paths."""
    from repro.core import distributed

    calls = {"n": 0}
    real = distributed.mesh_null_program

    def counting(*args, **kwargs):
        program = real(*args, **kwargs)

        def call(*a):
            calls["n"] += 1
            return program(*a)

        return call

    monkeypatch.setattr(distributed, "mesh_null_program", counting)

    x, y, _, f = problem
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    engine = CVEngine(EngineConfig(gram_impl="distributed", mesh=mesh))
    spec = DatasetSpec(x, f, LAM)
    w = Workload(kind="permutation", dataset=spec, y=y, n_perm=20, seed=4)
    events = list(stream_workload(engine, w, chunk=8))
    assert calls["n"] >= 3  # one sharded eval per chunk
    final = events[-1].payload
    streamed = jnp.concatenate([ev.payload for ev in events if ev.kind == "null"])
    np.testing.assert_array_equal(np.asarray(streamed), np.asarray(final.null))
    # identical draws to the mesh engine's monolithic path...
    _, plan = engine.resolve(spec)
    mono = engine.permutation_binary(plan, y, 20, jax.random.PRNGKey(4))
    np.testing.assert_allclose(np.asarray(final.null), np.asarray(mono.null), atol=1e-12)
    # ...and to a plain local engine
    local = CVEngine()
    _, lplan = local.plan(x, f, LAM)
    lmono = local.permutation_binary(lplan, y, 20, jax.random.PRNGKey(4))
    np.testing.assert_allclose(np.asarray(final.null), np.asarray(lmono.null), atol=1e-12)


def test_async_stream_equals_sync_stream(problem):
    x, y, _, f = problem
    spec = DatasetSpec(x, f, LAM)
    w = Workload(kind="permutation", dataset=spec, y=y, n_perm=16, seed=9)
    sync_events = list(stream_workload(CVEngine(), w, chunk=8))

    async def drive():
        async with Client(CVEngine(), transport="async", stream_chunk=8) as client:
            return [ev async for ev in client.stream(w)]

    async_events = asyncio.run(drive())
    assert [e.kind for e in async_events] == [e.kind for e in sync_events]
    np.testing.assert_array_equal(
        np.asarray(async_events[-1].payload.null),
        np.asarray(sync_events[-1].payload.null),
    )


# ---------------------------------------------------------------------------
# Client ergonomics
# ---------------------------------------------------------------------------


def test_client_transport_validation(problem):
    for unknown in ("carrier-pigeon", "thread"):  # the thread-queue transport is gone
        with pytest.raises(ValueError, match="sync.*async"):
            Client(transport=unknown)
    c = Client(transport="async")
    with pytest.raises(RuntimeError, match="async with"):
        with c:
            pass
    with pytest.raises(RuntimeError, match="must be entered"):
        c.submit(Workload(kind="tune", x=jnp.ones((4, 2)), y=jnp.ones(4)))


def test_client_gather_coalesces_sync(problem):
    x, y, _, f = problem
    engine = CVEngine()
    client = Client(engine)
    data = client.register(x, f, LAM)
    batch = [Workload(kind="cv", dataset=data, y=jnp.roll(y, i)) for i in range(4)]
    responses = client.gather(batch)
    assert len(responses) == 4
    assert engine.stats()["plans_built"] == 1
    ref = CVEngine()
    _, plan = ref.plan(x, f, LAM)
    for i, resp in enumerate(responses):
        want = ref.eval_binary(plan, jnp.stack([jnp.roll(y, j) for j in range(4)], 1))
        np.testing.assert_allclose(np.asarray(resp.values), np.asarray(want[..., i]),
                                   rtol=1e-9, atol=1e-12)
