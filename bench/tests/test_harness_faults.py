"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted in the engine the window drives, at a tiny size on
the CPU, and the rest of the run — window, reference check, verdict — is
the benchmark's own. The faults these cells can have: half of the batch
left out with the mean over the rest in its place (the draws of a null,
the folds of a cv evaluation), and an answer altered where it is made.
The cells run on one chip, so there is no exchange between chips to
leave out.
"""

import jax.numpy as jnp
import pytest
from repro.core.permutation import PermutationResult
from repro.serve import CVEngine

import bench.run as brun


def _half_mean(a, axis=0):
    a = jnp.asarray(a)
    h = max(1, a.shape[axis] // 2)
    rest = jnp.take(a, jnp.arange(h), axis=axis)
    fill = jnp.broadcast_to(rest.mean(axis=axis, keepdims=True).astype(a.dtype),
                            jnp.take(a, jnp.arange(h, a.shape[axis]), axis=axis).shape)
    return jnp.concatenate([rest, fill], axis=axis)


def _null_fault(monkeypatch, fault):
    orig_bin, orig_mc = CVEngine.null_binary, CVEngine.permutation_multiclass
    alter = _half_mean if fault == "half_batch" else (lambda a: jnp.roll(a, 1))

    def null_binary(self, *a, **kw):
        return alter(orig_bin(self, *a, **kw))

    def permutation_multiclass(self, *a, **kw):
        r = orig_mc(self, *a, **kw)
        return PermutationResult(r.observed, alter(r.null), r.p)

    monkeypatch.setattr(CVEngine, "null_binary", null_binary)
    monkeypatch.setattr(CVEngine, "permutation_multiclass", permutation_multiclass)


def _cv_fault(monkeypatch, fault):
    orig = CVEngine.eval_estimator

    def eval_estimator(self, plan, y, estimator, owned=False, **opts):
        out = orig(self, plan, y, estimator, owned=owned, **opts)
        if fault == "half_batch":
            return _half_mean(out)
        if estimator == "multiclass":  # the predictions moved to the next class
            return (out + 1) % opts["num_classes"]
        flat = jnp.abs(out).reshape(-1, *out.shape[2:]).argmax(0)
        k, i = jnp.unravel_index(flat, out.shape[:2])
        return out.at[k, i].multiply(-1.0)  # the largest decision value negated

    monkeypatch.setattr(CVEngine, "eval_estimator", eval_estimator)


CASES = [
    ("eeg_binary_p3800.perm1k", _null_fault, "half_batch"),
    ("eeg_binary_p3800.perm1k", _null_fault, "altered"),
    ("eeg_3class_p1900.group16", _null_fault, "half_batch"),
    ("eeg_3class_p1900.group16", _null_fault, "altered"),
    ("eeg_3class_p1900.group16", _cv_fault, "altered"),
    ("eeg_binary_p3800.http_cv", _cv_fault, "half_batch"),
    ("eeg_binary_p3800.http_cv", _cv_fault, "altered"),
]


@pytest.mark.parametrize("name,plant,fault", CASES,
                         ids=[f"{c[0]}-{c[1].__name__[1:]}-{c[2]}" for c in CASES])
def test_planted_fault_is_not_correct(monkeypatch, tiny_cell, name, plant, fault):
    cell = tiny_cell(name, rate_per_s=100.0)
    plant(monkeypatch, fault)
    res = brun.run_cell(cell, 2**31 + 11, 1.0, False)
    assert not res["correct"], res["checks"]
    failing = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert failing and "failed" not in failing
