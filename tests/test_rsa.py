"""Tests for repro.rsa and its serving integration: RDM exactness against
a NumPy reference, comparison statistics against scipy, permutation nulls,
the pairdist kernel path, searchlight sharding, and the engine's
no-recompile guarantee for RSA traffic."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import rsa
from repro.core import fastcv, folds as foldlib, multiclass, permutation
from repro.data import synthetic
from repro.serve import (Client, CVEngine, DatasetSpec, EngineConfig,
                         Workload, run_workloads)

C, N_PER, P, K, LAM = 5, 12, 150, 4, 1.0
N = C * N_PER


@pytest.fixture(scope="module")
def problem():
    x, y = synthetic.make_classification(jax.random.PRNGKey(0), N, P,
                                         num_classes=C, class_sep=2.0)
    f = foldlib.stratified_kfold(np.asarray(y), K, seed=1)
    return x, y, f


@pytest.fixture(scope="module")
def models(problem):
    x, y, _ = problem
    mu = rsa.condition_means(x, y, C)
    rng = np.random.default_rng(3)
    rnd = rng.normal(size=(C, C))
    rnd = np.abs(rnd + rnd.T)
    np.fill_diagonal(rnd, 0.0)
    return jnp.stack([rsa.euclidean_rdm(mu), jnp.asarray(rnd)])


# ---------------------------------------------------------------------------
# NumPy reference: hat matrix, Eq. 14/15 fold solves, pairwise scoring
# ---------------------------------------------------------------------------


def _np_reference_rdm(x, y_cond, folds, lam, dissimilarity="accuracy",
                      adjust_bias=True):
    x = np.asarray(x, dtype=np.float64)
    y_cond = np.asarray(y_cond)
    te_idx = np.asarray(folds.te_idx)
    tr_idx = np.asarray(folds.tr_idx)
    n = x.shape[0]
    xc = x - x.mean(axis=0, keepdims=True)
    g = xc @ xc.T
    hc = g @ np.linalg.inv(g + lam * np.eye(n))
    hc = 0.5 * (hc + hc.T)
    h = hc + np.full((n, n), 1.0 / n)

    rdm = np.zeros((C, C))
    for a in range(C):
        for b in range(a + 1, C):
            yy = np.where(y_cond == a, 1.0,
                          np.where(y_cond == b, -1.0, 0.0))
            e = yy - h @ yy
            hits, total = 0.0, 0.0
            pos_vals, neg_vals = [], []
            for k in range(te_idx.shape[0]):
                te, tr = te_idx[k], tr_idx[k]
                ih = np.eye(len(te)) - h[np.ix_(te, te)]
                e_dot_te = np.linalg.solve(ih, e[te])
                dv = yy[te] - e_dot_te
                if adjust_bias:
                    e_dot_tr = e[tr] + h[np.ix_(tr, te)] @ e_dot_te
                    y_dot_tr = yy[tr] - e_dot_tr
                    ptr, ntr = yy[tr] > 0, yy[tr] < 0
                    mu1 = y_dot_tr[ptr].mean() if ptr.any() else 0.0
                    mu2 = y_dot_tr[ntr].mean() if ntr.any() else 0.0
                    dv = dv - 0.5 * (mu1 + mu2)
                lab = yy[te]
                if dissimilarity == "accuracy":
                    pred = np.where(dv >= 0, 1.0, -1.0)
                    hits += np.sum((pred == lab) & (lab != 0))
                    total += np.sum(lab != 0)
                else:
                    pos_vals.extend(dv[lab > 0])
                    neg_vals.extend(dv[lab < 0])
            if dissimilarity == "accuracy":
                val = hits / max(total, 1.0)
            else:
                val = np.mean(pos_vals) - np.mean(neg_vals)
            rdm[a, b] = rdm[b, a] = val
    return rdm


# ---------------------------------------------------------------------------
# Serve-path exactness vs the NumPy reference (acceptance criterion)
# ---------------------------------------------------------------------------


def test_serve_rdm_matches_numpy_reference(problem):
    x, y, f = problem
    engine = CVEngine()
    (resp,) = run_workloads(
        engine, [Workload(kind="rsa", dataset=DatasetSpec(x, f, LAM), y=y, num_classes=C)]
    )
    want = _np_reference_rdm(x, y, f, LAM)
    np.testing.assert_allclose(np.asarray(resp.rdm), want, atol=1e-5)
    assert engine.stats()["plans_built"] == 1
    assert resp.pair_values.shape == (C * (C - 1) // 2,)


def test_serve_contrast_rdm_matches_numpy_reference(problem):
    x, y, f = problem
    engine = CVEngine()
    (resp,) = run_workloads(engine, [
        Workload(kind="rsa", dataset=DatasetSpec(x, f, LAM), y=y, num_classes=C,
                 dissimilarity="contrast", adjust_bias=False)])
    want = _np_reference_rdm(x, y, f, LAM, dissimilarity="contrast",
                             adjust_bias=False)
    np.testing.assert_allclose(np.asarray(resp.rdm), want, atol=1e-5)


def test_serve_rsa_scores_match_scipy(problem, models):
    scipy_stats = pytest.importorskip("scipy.stats")
    x, y, f = problem
    engine = CVEngine()
    responses = run_workloads(engine, [
        Workload(kind="rsa", dataset=DatasetSpec(x, f, LAM), y=y, num_classes=C,
                 model_rdms=models, comparison=method)
        for method in ("spearman", "kendall")])
    ev = np.asarray(rsa.upper_triangle(responses[0].rdm))
    mv = np.asarray(rsa.upper_triangle(models))
    for m in range(models.shape[0]):
        want_s = scipy_stats.spearmanr(ev, mv[m]).statistic
        want_k = scipy_stats.kendalltau(ev, mv[m]).statistic
        assert abs(float(responses[0].model_scores[m]) - want_s) < 1e-5
        assert abs(float(responses[1].model_scores[m]) - want_k) < 1e-5


def test_serve_rsa_multiclass_confusion(problem):
    x, y, f = problem
    engine = CVEngine()
    (resp,) = run_workloads(engine, [
        Workload(kind="rsa", dataset=DatasetSpec(x, f, LAM), y=y, num_classes=C,
                 contrast="multiclass")])
    plan = fastcv.prepare(x, f, LAM, with_train_block=True)
    preds = multiclass.batch_predict(plan, y[None, :], C)[0]
    want = rsa.rdm_from_confusion(preds, y[plan.te_idx], C)
    np.testing.assert_allclose(np.asarray(resp.rdm), np.asarray(want),
                               atol=1e-12)
    r = np.asarray(resp.rdm)
    assert np.allclose(r, r.T) and np.all(np.diag(r) == 0.0)
    assert np.all((r >= 0.0) & (r <= 1.0))


# ---------------------------------------------------------------------------
# No-recompile guarantee for RSA traffic (acceptance criterion)
# ---------------------------------------------------------------------------


def test_warm_rsa_batch_zero_recompiles(problem, models):
    x, y, f = problem
    spec = DatasetSpec(x, f, LAM)
    engine = CVEngine()
    # one warm-up of the batch shape (3 coalesced requests hit a larger
    # contrast-column bucket than a single request would)
    batch = [Workload(kind="rsa", dataset=spec, y=y, num_classes=C,
                      model_rdms=models, n_perm=17, seed=s)
             for s in range(3)]
    run_workloads(engine, batch)
    warm = engine.compile_count()
    # warm replay: same plan, same shape buckets, different seeds
    batch2 = [Workload(kind="rsa", dataset=spec, y=y, num_classes=C,
                       model_rdms=models, n_perm=20, seed=s)
              for s in range(5, 8)]
    responses = run_workloads(engine, batch2)
    assert engine.compile_count() == warm
    assert all(r.null.shape == (2, 20) for r in responses)
    # a second dataset with identical shapes also reuses every program
    x2, y2 = synthetic.make_classification(jax.random.PRNGKey(5), N, P,
                                           num_classes=C, class_sep=2.0)
    spec2 = DatasetSpec(x2, f, LAM)
    run_workloads(engine, [Workload(kind="rsa", dataset=spec2, y=y2, num_classes=C,
                            model_rdms=models, n_perm=20, seed=s)
                   for s in range(3)])
    assert engine.compile_count() == warm
    assert engine.stats()["plans_built"] == 2


def test_rsa_shares_plan_with_cv_requests(problem):
    x, y, f = problem
    spec = DatasetSpec(x, f, LAM)
    engine = CVEngine()
    y_bin = jnp.where(y % 2 == 0, -1.0, 1.0)
    run_workloads(engine, [Workload(kind="rsa", dataset=spec, y=y, num_classes=C),
                   Workload(kind="cv", dataset=spec, y=y_bin, estimator="binary"),
                   Workload(kind="cv", dataset=spec, y=y, estimator="multiclass", num_classes=C)])
    assert engine.stats()["plans_built"] == 1


# ---------------------------------------------------------------------------
# Comparison statistics + permutation nulls
# ---------------------------------------------------------------------------


def test_rankdata_and_correlations_handle_ties():
    scipy_stats = pytest.importorskip("scipy.stats")
    a = jnp.asarray([1.0, 2.0, 2.0, 3.0, 0.5, 2.0])
    b = jnp.asarray([0.1, 0.1, 5.0, 2.0, 2.0, 1.0])
    np.testing.assert_allclose(np.asarray(rsa.rankdata(a)),
                               scipy_stats.rankdata(np.asarray(a)))
    assert abs(float(rsa.spearman(a, b))
               - scipy_stats.spearmanr(np.asarray(a), np.asarray(b)).statistic) < 1e-12
    assert abs(float(rsa.kendall(a, b))
               - scipy_stats.kendalltau(np.asarray(a), np.asarray(b)).statistic) < 1e-12


def test_cosine_and_pearson():
    v = jnp.asarray([1.0, 2.0, 3.0])
    assert abs(float(rsa.cosine(v, 2.0 * v)) - 1.0) < 1e-12
    assert abs(float(rsa.pearson(v, -v)) + 1.0) < 1e-12


def test_permutation_null_engine_matches_library(problem, models):
    """Engine nulls (bucket-rounded T) are prefix-identical to direct
    library calls sharing the key — same contract as CV permutations."""
    x, y, f = problem
    engine = CVEngine()
    (resp,) = run_workloads(engine, [
        Workload(kind="rsa", dataset=DatasetSpec(x, f, LAM), y=y, num_classes=C,
                 model_rdms=models, n_perm=20, seed=7)])
    from repro.serve.batching import bucket_size
    perms = permutation.permutation_indices(jax.random.PRNGKey(7), C,
                                            bucket_size(20))
    want = rsa.permutation_null(resp.rdm, models, perms)[:, :20]
    np.testing.assert_allclose(np.asarray(resp.null), np.asarray(want),
                               atol=1e-12)
    assert resp.p.shape == (2,)
    assert np.all((np.asarray(resp.p) > 0.0) & (np.asarray(resp.p) <= 1.0))
    # a self-model must score (near) perfectly and be significant
    (self_resp,) = run_workloads(engine, [
        Workload(kind="rsa", dataset=DatasetSpec(x, f, LAM), y=y, num_classes=C,
                 model_rdms=resp.rdm[None], n_perm=63, seed=2)])
    assert float(self_resp.model_scores[0]) > 0.999


# ---------------------------------------------------------------------------
# Pattern RDMs (pairdist kernel) + searchlight sharding
# ---------------------------------------------------------------------------


def test_euclidean_rdm_impls_agree(problem):
    x, y, _ = problem
    mu = rsa.condition_means(x, y, C)
    d_xla = rsa.euclidean_rdm(mu, impl="xla")
    d_pal = rsa.euclidean_rdm(mu, impl="pallas")
    np.testing.assert_allclose(np.asarray(d_pal), np.asarray(d_xla),
                               rtol=1e-9, atol=1e-9)
    d = np.asarray(d_xla)
    assert np.allclose(d, d.T) and np.allclose(np.diag(d), 0.0)


def test_condition_means(problem):
    x, y, _ = problem
    mu = np.asarray(rsa.condition_means(x, y, C))
    for c in range(C):
        np.testing.assert_allclose(mu[c],
                                   np.asarray(x)[np.asarray(y) == c].mean(0),
                                   rtol=1e-12)


def test_searchlight_rdm_matches_per_problem(problem):
    x, y, f = problem
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    xs = jax.random.normal(jax.random.PRNGKey(4), (3, N, 48), jnp.float64)
    got = rsa.searchlight_rdm(xs, y, f, LAM, mesh, num_classes=C,
                              problem_axes=("data",))
    assert got.shape == (3, C, C)
    for q in range(3):
        want = rsa.rdm_binary(xs[q], y, f, C, LAM)
        np.testing.assert_allclose(np.asarray(got[q]), np.asarray(want),
                                   rtol=1e-9, atol=1e-9)


# ---------------------------------------------------------------------------
# Pair-contrast plumbing + the async server
# ---------------------------------------------------------------------------


def test_pair_contrast_columns(problem):
    _, y, _ = problem
    cols = np.asarray(rsa.pair_contrast_columns(y, C))
    pairs = rsa.condition_pairs(C)
    assert cols.shape == (N, C * (C - 1) // 2)
    y_np = np.asarray(y)
    for j, (a, b) in enumerate(pairs):
        np.testing.assert_array_equal(
            cols[:, j], np.where(y_np == a, 1.0,
                                 np.where(y_np == b, -1.0, 0.0)))


def test_rsa_through_engine_server(problem, models):
    x, y, f = problem
    spec = DatasetSpec(x, f, LAM)
    requests = [Workload(kind="rsa", dataset=spec, y=y, num_classes=C,
                         model_rdms=models, n_perm=10, seed=s)
                for s in range(4)]
    sync = run_workloads(CVEngine(), requests)

    async def main():
        async with Client(CVEngine(), transport="async", max_batch=4,
                          gather_window_ms=5.0) as client:
            return await client.gather(requests)

    results = asyncio.run(main())
    for got, want in zip(results, sync):
        np.testing.assert_allclose(np.asarray(got.rdm), np.asarray(want.rdm),
                                   rtol=1e-9, atol=1e-12)
        np.testing.assert_allclose(np.asarray(got.model_scores),
                                   np.asarray(want.model_scores),
                                   rtol=1e-9, atol=1e-12)


def test_oversized_plan_still_serves_rsa(problem):
    """Admission control end-to-end: a budget smaller than one plan serves
    the request un-cached without evicting anything."""
    x, y, f = problem
    engine = CVEngine(EngineConfig(cache_bytes=1024))     # tiny budget
    (resp,) = run_workloads(
        engine, [Workload(kind="rsa", dataset=DatasetSpec(x, f, LAM), y=y, num_classes=C)]
    )
    want = _np_reference_rdm(x, y, f, LAM)
    np.testing.assert_allclose(np.asarray(resp.rdm), want, atol=1e-5)
    stats = engine.stats()
    assert stats["oversized"] >= 1
    assert stats["bytes_in_use"] == 0 and stats["evictions"] == 0
