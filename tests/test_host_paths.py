"""Regression tests for the host-path fixes reprolint (RL001/RL004) drove.

Each test pins the *behavior* behind a flagged-and-fixed site: streamed
null assembly and batch update coalescing now run on the host
(``np.concatenate`` over ``np.asarray`` chunks) instead of eager ``jnp``
assembly, and the engine's stat counters are mutated under ``_lock``.
The numeric contract is that the host path is bit-identical to the old
device path — the device-to-host transfer preserves every bit and the
dtype — so every comparison here is exact.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import folds as foldlib
from repro.data import synthetic
from repro.serve import (
    CVEngine,
    DatasetSpec,
    EngineConfig,
    Workload,
    run_workloads,
    stream_workload,
)

N, P, K, LAM = 48, 96, 4, 1.0


def _problem(seed=0):
    x, yc = synthetic.make_classification(
        jax.random.PRNGKey(seed), N, P, num_classes=3, class_sep=2.0
    )
    y = jnp.where(yc % 2 == 0, -1.0, 1.0)
    return x, y, yc, foldlib.kfold(N, K, seed=1)


# ---------------------------------------------------------------------------
# RL004 fix: stat counters are exact under concurrent submissions
# ---------------------------------------------------------------------------


def test_stat_counters_exact_under_concurrent_submissions():
    x, y, _, f = _problem()
    workers, per_worker = 8, 6

    # Measure the per-call increment on a warm serial engine first, so the
    # threaded assertion is exact rather than a lower bound.
    serial = CVEngine()
    h = serial.register(x, f, LAM)
    w = Workload(kind="cv", dataset=h, y=y)
    run_workloads(serial, [w])  # absorb plan build + first-shape compiles
    before = serial.stats()["labels_evaluated"]
    run_workloads(serial, [w])
    per_call = serial.stats()["labels_evaluated"] - before
    assert per_call > 0

    engine = CVEngine()
    handle = engine.register(x, f, LAM)
    wt = Workload(kind="cv", dataset=handle, y=y)
    run_workloads(engine, [wt])  # warm the plan so threads only contend on evals
    start = engine.stats()["labels_evaluated"]
    barrier = threading.Barrier(workers)

    def drive():
        barrier.wait()
        for _ in range(per_worker):
            run_workloads(engine, [wt])

    threads = [threading.Thread(target=drive) for _ in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    got = engine.stats()["labels_evaluated"] - start
    assert got == workers * per_worker * per_call
    assert engine.stats()["plans_built"] == 1  # the warmup build, exactly once


# ---------------------------------------------------------------------------
# RL001 fix: streamed permutation nulls assemble on the host, bit-exactly
# ---------------------------------------------------------------------------


def test_streamed_permutation_null_is_host_and_bit_exact():
    x, y, _, f = _problem()
    engine = CVEngine()
    spec = DatasetSpec(x, f, LAM)
    w = Workload(kind="permutation", dataset=spec, y=y, n_perm=20, seed=4)
    events = list(stream_workload(engine, w, chunk=8))
    final = events[-1].payload

    # The assembled null is a host array...
    assert isinstance(final.null, np.ndarray)
    # ...bit-identical to its own streamed chunks...
    streamed = np.concatenate(
        [np.asarray(ev.payload) for ev in events if ev.kind == "null"]
    )
    np.testing.assert_array_equal(streamed, final.null)
    # ...and to the monolithic engine entry point (same seed, same draws).
    _, plan = engine.resolve(spec)
    mono = engine.permutation_binary(plan, y, 20, jax.random.PRNGKey(4))
    assert final.null.dtype == np.asarray(mono.null).dtype  # transfer keeps dtype
    np.testing.assert_array_equal(final.null, np.asarray(mono.null))
    np.testing.assert_array_equal(np.asarray(final.p), np.asarray(mono.p))


def test_streamed_rsa_null_and_p_match_batch_exactly():
    x, _, yc, f = _problem()
    rdm = np.abs(np.arange(3)[:, None] - np.arange(3)[None, :]).astype(np.float64)
    engine = CVEngine()
    spec = DatasetSpec(x, f, LAM)
    w = Workload(
        kind="rsa",
        dataset=spec,
        y=yc,
        num_classes=3,
        model_rdms=rdm[None],
        n_perm=12,
        seed=7,
    )
    events = list(stream_workload(engine, w, chunk=4))
    final = events[-1].payload
    assert isinstance(final.null, np.ndarray)

    (batch,) = run_workloads(CVEngine(), [w])
    np.testing.assert_array_equal(final.null, np.asarray(batch.null))
    np.testing.assert_array_equal(np.asarray(final.p), np.asarray(batch.p))
    np.testing.assert_array_equal(
        np.asarray(final.model_scores), np.asarray(batch.model_scores)
    )


# ---------------------------------------------------------------------------
# RL001 fix: batch update coalescing stacks appends on the host
# ---------------------------------------------------------------------------


def test_update_batch_coalescing_matches_single_concatenated_update():
    x, _, _, f = _problem()
    rng = np.random.default_rng(3)
    x1 = rng.normal(size=(K, P))
    x2 = rng.normal(size=(K, P))

    coalesced = CVEngine(EngineConfig(cache_bytes=64 << 20))
    h0 = coalesced.register(x, f, LAM)
    r1, r2 = run_workloads(
        coalesced,
        [
            Workload(kind="update", dataset=h0, x=x1),
            Workload(kind="update", dataset=h0, x=x2),
        ],
    )
    # One rank-2K correction: both members share the same version-1 handle
    # with their own appended counts.
    assert r1.handle.key == r2.handle.key and r1.handle.version == 1
    assert (r1.appended, r2.appended) == (K, K)
    assert coalesced.stats()["plans_updated"] == 1

    single = CVEngine(EngineConfig(cache_bytes=64 << 20))
    g0 = single.register(x, f, LAM)
    g1 = single.update_dataset(g0, x_new=np.concatenate([x1, x2]))
    assert g1.n == r1.handle.n == N + 2 * K

    np.testing.assert_array_equal(
        np.asarray(coalesced.dataset_record(r1.handle).x),
        np.asarray(single.dataset_record(g1).x),
    )
