"""Tests for the repro.serve subsystem: engine exactness, plan-cache
eviction, micro-batcher round-trips, and the no-recompile guarantee.

Written against the unified Workload API (the deprecated request shims
were removed at 0.3; see the README migration table)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fastcv, folds as foldlib, multiclass, permutation, regression
from repro.data import synthetic
from repro.serve import (CVEngine, DatasetSpec, EngineConfig, EngineServer,
                         MicroBatcher, PlanCache, Workload, bucket_size,
                         serve)

N, P, K, LAM = 48, 96, 4, 1.0


@pytest.fixture(scope="module")
def problem():
    x, yc = synthetic.make_classification(jax.random.PRNGKey(0), N, P,
                                          num_classes=3, class_sep=2.0)
    y = jnp.where(yc % 2 == 0, -1.0, 1.0)
    f = foldlib.kfold(N, K, seed=1)
    return x, y, yc, f


@pytest.fixture()
def engine():
    return CVEngine(EngineConfig(cache_bytes=64 << 20))


# ---------------------------------------------------------------------------
# Engine results are bit-identical to the direct library calls
# ---------------------------------------------------------------------------


def test_engine_binary_bit_identical(problem, engine):
    x, y, _, f = problem
    _, plan = engine.plan(x, f, LAM)
    dv_direct, _ = fastcv.binary_cv(x, y, f, lam=LAM)
    dv_engine = engine.eval_binary(plan, y)
    assert dv_direct.shape == dv_engine.shape
    assert bool(jnp.all(dv_direct == dv_engine))


def test_engine_multiclass_bit_identical(problem, engine):
    x, _, yc, f = problem
    _, plan = engine.plan(x, f, LAM)
    pred_direct, y_te = multiclass.analytical_cv_multiclass(x, yc, f, 3, LAM)
    pred_engine = engine.eval_multiclass(plan, yc, 3)
    assert bool(jnp.all(pred_direct == pred_engine))


def test_engine_ridge_bit_identical(problem, engine):
    x, y, _, f = problem
    # ridge is served from the superset (train-block) plan when cached
    _, plan = engine.plan(x, f, LAM)
    r_direct, _ = regression.analytical_cv(x, y, f, lam=LAM)
    r_engine = engine.eval_ridge(plan, y)
    assert bool(jnp.all(r_direct == r_engine))


def test_engine_batched_columns_match_singles(problem, engine):
    """Each column of a (N, B) batch matches the single-query answer.

    Only numerically (tight tolerance), not bitwise: XLA blocks the H·Y
    matmul differently for different padded batch shapes."""
    x, y, _, f = problem
    _, plan = engine.plan(x, f, LAM)
    cols = jnp.stack([y, -y, jnp.roll(y, 3)], axis=1)
    batched = engine.eval_binary(plan, cols)
    for b in range(cols.shape[1]):
        single = engine.eval_binary(plan, cols[:, b])
        np.testing.assert_allclose(np.asarray(batched[..., b]),
                                   np.asarray(single), rtol=1e-9, atol=1e-12)


def test_engine_permutation_matches_library(problem, engine):
    x, y, _, f = problem
    _, plan = engine.plan(x, f, LAM)
    key = jax.random.PRNGKey(7)
    res_e = engine.permutation_binary(plan, y, 20, key)
    res_l = permutation.analytical_permutation_binary(x, y, f, LAM, 20, key)
    np.testing.assert_allclose(np.asarray(res_e.null), np.asarray(res_l.null),
                               atol=1e-12)
    assert abs(float(res_e.observed) - float(res_l.observed)) < 1e-12
    assert abs(float(res_e.p) - float(res_l.p)) < 1e-12


@pytest.mark.parametrize("estimator", ["binary", "multiclass"])
def test_null_draw_counters_count_every_cv_null(problem, engine, estimator):
    """Both CV nulls count the draws asked for and the bucket's padding."""
    x, y, yc, f = problem
    _, plan = engine.plan(x, f, LAM)
    key = jax.random.PRNGKey(3)
    if estimator == "binary":
        engine.permutation_binary(plan, y, 20, key)
    else:
        engine.permutation_multiclass(plan, yc, 20, key, num_classes=3)
    draws = engine.metrics.get("null_draws_total").value(path="local")
    pad = engine.metrics.get("null_pad_draws_total").value(path="local")
    assert (draws, pad) == (20, bucket_size(20, engine.config.buckets) - 20)


def test_engine_gram_impl_pallas_matches_xla(problem):
    x, y, _, f = problem
    e_xla = CVEngine(EngineConfig(gram_impl="xla"))
    e_pal = CVEngine(EngineConfig(gram_impl="pallas"))
    _, p_xla = e_xla.plan(x, f, LAM)
    _, p_pal = e_pal.plan(x, f, LAM)
    np.testing.assert_allclose(np.asarray(p_xla.h), np.asarray(p_pal.h),
                               atol=1e-10)


# ---------------------------------------------------------------------------
# Plan cache: LRU under a byte budget
# ---------------------------------------------------------------------------


def _dummy_plan(n=32, k=2, m=8):
    z = jnp.zeros
    return fastcv.CVPlan(z((n, n)), z((k, m), jnp.int32),
                         z((k, n - m), jnp.int32), z((k, m, m)), None)


def test_cache_eviction_respects_byte_budget():
    one = _dummy_plan().nbytes
    cache = PlanCache(byte_budget=2 * one + one // 2)   # fits exactly two
    cache.put("a", _dummy_plan())
    cache.put("b", _dummy_plan())
    assert cache.stats.evictions == 0
    cache.put("c", _dummy_plan())                        # evicts LRU = "a"
    assert cache.stats.evictions == 1
    assert cache.stats.bytes_in_use <= cache.stats.byte_budget
    assert "a" not in cache and "b" in cache and "c" in cache


def test_cache_lru_order_respects_recency():
    one = _dummy_plan().nbytes
    cache = PlanCache(byte_budget=2 * one + one // 2)
    cache.put("a", _dummy_plan())
    cache.put("b", _dummy_plan())
    assert cache.get("a") is not None                    # refresh "a"
    cache.put("c", _dummy_plan())                        # now evicts "b"
    assert "b" not in cache and "a" in cache and "c" in cache
    assert cache.stats.hits == 1


def test_cache_rejects_oversized_plan():
    """Admission control: a plan that can never fit is served un-cached."""
    one = _dummy_plan().nbytes
    cache = PlanCache(byte_budget=one // 2)
    admitted = cache.put("big", _dummy_plan())
    assert not admitted
    assert "big" not in cache
    assert cache.stats.oversized == 1
    assert cache.stats.bytes_in_use == 0


def test_cache_oversized_plan_does_not_evict_residents():
    """An oversized build must NOT flush the cache to make room it can
    never use (ROADMAP admission-control item)."""
    one = _dummy_plan().nbytes
    cache = PlanCache(byte_budget=2 * one + one // 2)
    cache.put("a", _dummy_plan())
    cache.put("b", _dummy_plan())
    big = _dummy_plan(n=96, k=4, m=24)                   # > whole budget
    assert big.nbytes > cache.stats.byte_budget
    plan, was_hit = cache.get_or_build("big", lambda: big)
    assert plan is big and not was_hit                   # still served
    assert "big" not in cache
    assert "a" in cache and "b" in cache                 # residents survive
    assert cache.stats.evictions == 0
    assert cache.stats.oversized == 1
    # the counter keeps counting on repeat builds (it never becomes a hit)
    cache.get_or_build("big", lambda: big)
    assert cache.stats.oversized == 2


def test_cache_pin_exempts_from_eviction_and_pressure():
    one = _dummy_plan().nbytes
    cache = PlanCache(byte_budget=2 * one + one // 2)   # fits exactly two
    cache.put("a", _dummy_plan())
    assert cache.pin("a")
    assert cache.stats.pinned == 1 and cache.stats.pinned_bytes == one
    cache.put("b", _dummy_plan())
    cache.put("c", _dummy_plan())
    cache.put("d", _dummy_plan())
    # "a" is LRU by recency but pinned: "b" is evicted instead, and the
    # pinned bytes don't count against the pressure budget
    assert "a" in cache
    assert "b" not in cache and "c" in cache and "d" in cache
    assert cache.stats.bytes_in_use - cache.stats.pinned_bytes <= cache.stats.byte_budget
    # pin is idempotent; pinning a missing key is a no-op
    assert cache.pin("a") and cache.stats.pinned == 1
    assert not cache.pin("zzz")


def test_cache_unpin_resubjects_to_pressure():
    one = _dummy_plan().nbytes
    cache = PlanCache(byte_budget=2 * one + one // 2)
    cache.put("a", _dummy_plan())
    cache.pin("a")
    cache.put("b", _dummy_plan())
    cache.put("c", _dummy_plan())
    assert len(cache) == 3                               # a pinned + b + c
    assert cache.unpin("a")
    assert not cache.unpin("a")                          # already unpinned
    assert cache.stats.pinned == 0 and cache.stats.pinned_bytes == 0
    # unpinned "a" counts again: 3 * one > budget -> one eviction, and "a"
    # itself was refreshed most-recent so the LRU victim is "b"
    assert len(cache) == 2
    assert "a" in cache and "b" not in cache
    cache.clear()
    assert cache.stats.pinned == 0 and len(cache) == 0


def test_engine_cache_eviction_end_to_end(problem):
    x, y, _, f = problem
    _, probe = CVEngine().plan(x, f, LAM)
    engine = CVEngine(EngineConfig(cache_bytes=2 * probe.nbytes + 1))
    for lam in (0.5, 1.0, 2.0, 4.0):                     # 4 distinct plans
        engine.plan(x, f, lam)
    stats = engine.stats()
    assert stats["evictions"] >= 2
    assert stats["bytes_in_use"] <= stats["byte_budget"]


# ---------------------------------------------------------------------------
# Micro-batcher: ragged round-trips
# ---------------------------------------------------------------------------


def test_bucket_size():
    assert bucket_size(1) == 1
    assert bucket_size(3) == 4
    assert bucket_size(33) == 64
    assert bucket_size(1024) == 1024
    assert bucket_size(1500) == 2048                     # multiple of top


def test_batcher_ragged_columns_round_trip():
    mb = MicroBatcher()
    n = 10
    rng = np.random.default_rng(0)
    widths = [1, 3, 2, 5]
    ys = [jnp.asarray(rng.normal(size=(n,)))] + [
        jnp.asarray(rng.normal(size=(n, w))) for w in widths[1:]]
    outs = mb.run_columns(ys, lambda batch: batch * 2.0)
    assert outs[0].shape == (n,)
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(ys[0]) * 2)
    for y, out, w in zip(ys[1:], outs[1:], widths[1:]):
        assert out.shape == (n, w)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(y) * 2)


def test_batcher_ragged_rows_round_trip():
    mb = MicroBatcher()
    n = 10
    ys = [jnp.arange(n), jnp.stack([jnp.arange(n)] * 3) + 1,
          jnp.arange(n)[None, :] + 2]
    outs = mb.run_rows(ys, lambda batch: batch + 100)
    assert outs[0].shape == (n,)
    assert outs[1].shape == (3, n)
    assert outs[2].shape == (1, n)
    np.testing.assert_array_equal(np.asarray(outs[0]),
                                  np.arange(n) + 100)


def test_plan_key_distinguishes_train_indices(problem):
    """Same te_idx but different tr_idx must NOT collide in the cache:
    the plan's train blocks and bias adjustment depend on tr_idx."""
    x, _, _, f = problem
    f2 = foldlib.Folds.with_indices(f.te_idx, f.tr_idx[:, ::2])
    assert fastcv.plan_key(x, f, LAM) != fastcv.plan_key(x, f2, LAM)
    engine = CVEngine()
    _, p1 = engine.plan(x, f, LAM)
    _, p2 = engine.plan(x, f2, LAM)
    assert engine.stats()["plans_built"] == 2
    assert p1.tr_idx.shape != p2.tr_idx.shape


def test_permutation_indices_prefix_stable():
    """Larger T (bucket rounding in the engine) keeps the leading rows."""
    key = jax.random.PRNGKey(3)
    small = permutation.permutation_indices(key, 48, 20)
    big = permutation.permutation_indices(key, 48, 32)
    np.testing.assert_array_equal(np.asarray(small), np.asarray(big[:20]))


def test_folds_with_indices_matches_kfold(problem):
    x, y, _, f = problem
    f2 = foldlib.Folds.with_indices(f.te_idx, f.tr_idx)
    assert f2.k == f.k and f2.test_size == f.test_size
    dv1, _ = fastcv.binary_cv(x, y, f, lam=LAM)
    dv2, _ = fastcv.binary_cv(x, y, f2, lam=LAM)
    assert bool(jnp.all(dv1 == dv2))


# ---------------------------------------------------------------------------
# No-recompile guarantee (compile-counter assertion)
# ---------------------------------------------------------------------------


def test_second_same_bucket_request_triggers_no_recompile(problem, engine):
    x, y, _, f = problem
    _, plan = engine.plan(x, f, LAM)
    engine.permutation_binary(plan, y, 17, jax.random.PRNGKey(0))
    warm = engine.compile_count()
    # different T, same bucket (32); different seed; same plan
    engine.permutation_binary(plan, y, 23, jax.random.PRNGKey(1))
    engine.permutation_binary(plan, y, 30, jax.random.PRNGKey(2))
    assert engine.compile_count() == warm
    # a second *dataset* with identical shapes also reuses the programs
    x2, yc2 = synthetic.make_classification(jax.random.PRNGKey(9), N, P)
    y2 = jnp.where(yc2 == 0, -1.0, 1.0)
    _, plan2 = engine.plan(x2, f, LAM)
    engine.permutation_binary(plan2, y2, 20, jax.random.PRNGKey(3))
    assert engine.compile_count() == warm


def test_cv_eval_no_recompile_across_batch_sizes(problem, engine):
    x, y, _, f = problem
    _, plan = engine.plan(x, f, LAM)
    engine.eval_binary(plan, jnp.stack([y] * 3, axis=1))    # bucket 4
    warm = engine.compile_count()
    engine.eval_binary(plan, jnp.stack([y] * 4, axis=1))    # same bucket
    engine.eval_binary(plan, y[:, None])                    # bucket 1: +1
    engine.eval_binary(plan, y)                             # bucket 1 again
    assert engine.compile_count() == warm + 1


# ---------------------------------------------------------------------------
# Driver + threaded server
# ---------------------------------------------------------------------------


def _requests(problem, n_perm=12):
    x, y, yc, f = problem
    spec = DatasetSpec(x, f, LAM)
    return [
        Workload(kind="cv", dataset=spec, y=y, estimator="binary"),
        Workload(kind="cv", dataset=spec, y=-y, estimator="binary"),
        Workload(kind="cv", dataset=spec, y=y, estimator="ridge"),
        Workload(kind="cv", dataset=spec, y=yc, estimator="multiclass", num_classes=3),
        Workload(kind="permutation", dataset=spec, y=y, n_perm=n_perm, seed=4),
        Workload(kind="tune", x=x, y=y),
    ]


def test_serve_driver_mixed_batch(problem):
    x, y, yc, f = problem
    engine = CVEngine()
    responses = serve(engine, _requests(problem))
    dv, _ = fastcv.binary_cv(x, y, f, lam=LAM)
    # coalesced into a (N, 2) batch -> numerically equal, not bitwise
    np.testing.assert_allclose(np.asarray(responses[0].values),
                               np.asarray(dv), rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(np.asarray(responses[1].values),
                               np.asarray(-dv), rtol=1e-9, atol=1e-12)
    pred, _ = multiclass.analytical_cv_multiclass(x, yc, f, 3, LAM)
    assert bool(jnp.all(responses[3].values == pred))
    assert responses[4].null.shape == (12,)
    assert 0.0 < float(responses[4].p) <= 1.0
    assert float(responses[5].result.best_lambda) > 0.0
    # whole mixed batch shares ONE plan build
    assert engine.stats()["plans_built"] == 1


def test_serve_raw_index_folds(problem):
    """Requests may carry bare (te_idx, tr_idx) arrays instead of Folds."""
    x, y, _, f = problem
    spec = DatasetSpec(x, (np.asarray(f.te_idx), np.asarray(f.tr_idx)), LAM)
    engine = CVEngine()
    (resp,) = serve(engine, [Workload(kind="cv", dataset=spec, y=y, estimator="binary")])
    dv, _ = fastcv.binary_cv(x, y, f, lam=LAM)
    assert bool(jnp.all(resp.values == dv))


def test_threaded_server_matches_sync(problem):
    engine = CVEngine()
    requests = _requests(problem) * 3
    sync = serve(CVEngine(), requests)
    with EngineServer(engine, max_batch=8, max_wait_ms=5.0) as server:
        futures = [server.submit(r) for r in requests]
        results = [fu.result(timeout=300) for fu in futures]
    assert server.requests_served == len(requests)
    for got, want in zip(results, sync):
        assert type(got) is type(want)
        # worker micro-batches may split differently than one sync batch,
        # so padded shapes (and hence last-bit rounding) can differ
        if hasattr(want, "values"):
            np.testing.assert_allclose(np.asarray(got.values),
                                       np.asarray(want.values),
                                       rtol=1e-9, atol=1e-12)
        elif hasattr(want, "null"):
            np.testing.assert_allclose(np.asarray(got.null),
                                       np.asarray(want.null),
                                       rtol=1e-9, atol=1e-12)


def test_threaded_server_propagates_errors(problem):
    x, y, _, f = problem
    engine = CVEngine()
    # Workload validates estimator names eagerly, so smuggle an invalid one
    # past construction to exercise the serve-time error path through the
    # server's futures.
    bad = Workload(kind="cv", dataset=DatasetSpec(x, f, LAM), y=y)
    object.__setattr__(bad, "estimator", "nonsense")
    with EngineServer(engine) as server:
        fut = server.submit(bad)
        with pytest.raises(ValueError):
            fut.result(timeout=300)


def test_workload_rejects_unknown_estimator_eagerly(problem):
    x, y, _, f = problem
    with pytest.raises(ValueError):
        Workload(kind="cv", dataset=DatasetSpec(x, f, LAM), y=y,
                 estimator="nonsense")


def test_engine_distributed_paths_single_device(problem):
    """gram_impl='distributed' + mesh-sharded permutations on a 1-device
    mesh must agree with the local paths (real multi-device coverage lives
    in tests/distributed_worker.py)."""
    x, y, _, f = problem
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    e_dist = CVEngine(EngineConfig(gram_impl="distributed", mesh=mesh))
    e_loc = CVEngine()
    _, p_dist = e_dist.plan(x, f, LAM)
    _, p_loc = e_loc.plan(x, f, LAM)
    np.testing.assert_allclose(np.asarray(p_dist.h), np.asarray(p_loc.h),
                               atol=1e-10)
    key = jax.random.PRNGKey(11)
    r_dist = e_dist.permutation_binary(p_dist, y, 10, key)
    r_loc = e_loc.permutation_binary(p_loc, y, 10, key)
    np.testing.assert_allclose(np.asarray(r_dist.null),
                               np.asarray(r_loc.null), atol=1e-12)
