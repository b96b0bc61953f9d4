"""One permutation test, one program, one fetch.

``CVEngine.permutation_binary`` / ``permutation_multiclass`` run the draws,
the observed evaluation, the null and the p-value as one jitted program
per shape bucket and read the packed result back with one transfer. These
tests hold that path to the library and to the chunked (streamed) path,
check that a bucket compiles once whatever ``n_perm`` it serves, that the
response holds host arrays, that the engagement counter counts each test,
that the host-made key is JAX's and that the program's null is the
engine's own null method.
"""

import jax
import numpy as np
import pytest

from repro.core import folds as foldlib
from repro.core import permutation
from repro.data import synthetic
from repro.serve import CVEngine, DatasetSpec, Workload, bucket_size, run_workloads
from repro.serve.workload import stream_workload

N, P, K, LAM, N_PERM, SEED = 48, 96, 4, 1.0, 20, 7


@pytest.fixture(scope="module")
def problem():
    x, yc = synthetic.make_classification(jax.random.PRNGKey(0), N, P,
                                          num_classes=3, class_sep=2.0)
    y = np.where(np.asarray(yc) % 2 == 0, -1.0, 1.0)
    return x, y, np.asarray(yc), foldlib.kfold(N, K, seed=1)


def _workload(spec, y, yc, estimator, n_perm=N_PERM, seed=SEED):
    if estimator == "multiclass":
        return Workload(kind="permutation", dataset=spec, y=yc, estimator="multiclass",
                        num_classes=3, n_perm=n_perm, seed=seed)
    return Workload(kind="permutation", dataset=spec, y=y, n_perm=n_perm, seed=seed)


def _local_case(problem, estimator):
    """(one-program result, {reference name: result}) on one device."""
    x, y, yc, f = problem
    engine = CVEngine()
    _, plan = engine.plan(x, f, LAM)
    key = jax.random.PRNGKey(SEED)
    if estimator == "multiclass":
        one = engine.permutation_multiclass(plan, yc, N_PERM, SEED, num_classes=3)
        lib = permutation.analytical_permutation_multiclass(x, yc, f, 3, LAM, N_PERM, key)
    else:
        one = engine.permutation_binary(plan, y, N_PERM, SEED)
        lib = permutation.analytical_permutation_binary(x, y, f, LAM, N_PERM, key)
    spec = DatasetSpec(x, f, LAM)
    stream = list(stream_workload(CVEngine(), _workload(spec, y, yc, estimator), chunk=8))
    return one, {"library": lib, "stream": stream[-1].payload}


@pytest.mark.parametrize("estimator", ["binary", "multiclass"])
def test_one_program_matches_library_and_chunked_path(problem, estimator):
    # the (2, 2) mesh case is tests/test_mesh_engine.py's, on its worker's readings
    one, refs = _local_case(problem, estimator)
    assert np.asarray(one.null).shape == (N_PERM,)
    for name, ref in refs.items():
        np.testing.assert_allclose(np.asarray(one.null), np.asarray(ref.null), rtol=0,
                                   atol=1e-12, err_msg=name)
        assert abs(float(one.observed) - float(ref.observed)) < 1e-12, name
        assert abs(float(one.p) - float(ref.p)) < 1e-12, name


@pytest.mark.parametrize("estimator", ["binary", "multiclass"])
def test_n_perm_within_one_bucket_compiles_one_program(problem, estimator):
    x, y, yc, f = problem
    engine = CVEngine()
    _, plan = engine.plan(x, f, LAM)
    assert {bucket_size(n, engine.config.buckets) for n in (17, 23, 30, 32)} == {32}
    before = engine.compile_count()
    for i, n_perm in enumerate((17, 23, 30, 32)):
        if estimator == "multiclass":
            res = engine.permutation_multiclass(plan, yc, n_perm, i, num_classes=3)
        else:
            res = engine.permutation_binary(plan, y, n_perm, i)
        assert res.null.shape == (n_perm,)
        assert engine.compile_count() == before + 1  # the first request's program only
    assert len(engine._perm_tests) == 1


@pytest.mark.parametrize("estimator", ["binary", "multiclass"])
def test_response_reads_transfer_nothing(problem, estimator):
    x, y, yc, f = problem
    engine = CVEngine()
    (resp,) = run_workloads(engine, [_workload(DatasetSpec(x, f, LAM), y, yc, estimator)])
    with jax.transfer_guard_device_to_host("disallow"):
        null = np.asarray(resp.null)
        observed, p = float(resp.observed), float(resp.p)
    assert null.shape == (N_PERM,) and 0.0 < p <= 1.0 and 0.0 <= observed <= 1.0


def test_programs_counter_rises_with_permutation_requests(problem):
    x, y, yc, f = problem
    engine = CVEngine()
    spec = DatasetSpec(x, f, LAM)
    programs = engine.metrics.get("permutation_programs_total")
    requests = engine.metrics.get("requests_total")
    for i, estimator in enumerate(["binary", "multiclass", "binary"], start=1):
        run_workloads(engine, [_workload(spec, y, yc, estimator, seed=i)])
        served = (requests.value(kind="permutation", estimator="binary")
                  + requests.value(kind="permutation", estimator="multiclass"))
        assert programs.value(path="local") == served == i
    assert programs.value(path="mesh") == 0


SEEDS = [0, 1, -1, 7, 2**31 - 1, 2**31 + 11, 2**32 - 1, 2**32, 2**32 + 7, 2**40 + 3,
         -2**31 - 1, 2**63 - 1, -2**63]


@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
def test_host_key_is_jax_key_bit_for_bit(x64):
    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", x64)
    try:
        for seed in SEEDS:
            want = np.asarray(jax.random.PRNGKey(seed))
            got = permutation.prng_key(seed)
            assert got.dtype == want.dtype and np.array_equal(got, want), seed
    finally:
        jax.config.update("jax_enable_x64", before)


@pytest.mark.parametrize("estimator", ["binary", "multiclass"])
def test_one_program_null_is_the_engines_null(monkeypatch, problem, estimator):
    """The one program traces ``CVEngine.null_binary`` / ``null_multiclass``
    as its null: what they give is the test's null, and the p-value counts it."""
    x, y, yc, f = problem
    name = "null_multiclass" if estimator == "multiclass" else "null_binary"
    orig = getattr(CVEngine, name)

    def run():
        engine = CVEngine()
        _, plan = engine.plan(x, f, LAM)
        if estimator == "multiclass":
            return engine.permutation_multiclass(plan, yc, N_PERM, SEED, num_classes=3)
        return engine.permutation_binary(plan, y, N_PERM, SEED)

    want = run()
    monkeypatch.setattr(CVEngine, name, lambda self, *a, **kw: 1.0 - orig(self, *a, **kw))
    got = run()
    np.testing.assert_allclose(got.null, 1.0 - want.null, rtol=0, atol=1e-12)
    assert got.observed == want.observed
    assert got.p == permutation.p_value(got.observed, got.null)
