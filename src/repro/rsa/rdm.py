"""Cross-validated representational dissimilarity matrices (RDMs).

The paper names Representational Similarity Analysis as a headline
application of analytical CV (§1, §4.2): once the hat matrix and per-fold
factorisations are built, *every* contrast between conditions is just
another label column through the cached fold solves, at O(K·m²) each.

This module makes that concrete. Conditions are integer labels
``y_cond ∈ [0, C)`` over the N samples; the empirical RDM is built from
one shared :class:`~repro.core.fastcv.CVPlan`:

* **binary contrasts** — each of the B = C(C−1)/2 condition pairs (a, b)
  becomes one ±1/0 label column (+1 on a's samples, −1 on b's, 0
  elsewhere). All B columns ride a *single* batched fold solve
  (``fastcv.cv_errors`` broadcasts over the trailing dim), and each pair's
  dissimilarity is scored from the cross-validated decision values:
  ``"accuracy"`` (cross-validated pairwise decodability, with the paper's
  §2.5 LDA bias correction computed from the training-fold decision
  values) or ``"contrast"`` (the cross-validated mean decision-value
  contrast — a continuous, crossnobis-flavoured measure).
* **multi-class contrasts** — one Algorithm-2 multi-class CV run; the
  RDM is the symmetrised confusion dissimilarity 1 − (p(b|a) + p(a|b))/2.

Non-cross-validated baselines (condition-mean Euclidean RDMs, also the
usual way to *construct* model RDMs from feature embeddings) route through
the Pallas ``pairdist`` kernel on TPU. Searchlight sweeps — Q independent
RDM problems — shard over the mesh's problem axes via
:func:`repro.core.distributed.sharded_problems`.
"""

from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fastcv, metrics, multiclass
from repro.core.folds import Folds
from repro.kernels.common import dot_precision

__all__ = [
    "RDMCache",
    "condition_pairs",
    "pair_contrast_columns",
    "pair_dissimilarities",
    "rdm_from_pair_values",
    "rdm_binary",
    "rdm_from_confusion",
    "rdm_multiclass",
    "condition_means",
    "ring_rdm",
    "euclidean_rdm",
    "searchlight_rdm",
    "make_eval_pairs",
]

_DISSIMILARITIES = ("accuracy", "contrast")


class RDMCache:
    """Memoised empirical RDMs, keyed by (plan, labels-fingerprint, spec).

    An empirical RDM is a pure function of the plan (features × folds × λ)
    and the condition labels — so repeated model-RDM scoring against the
    same data (a model-comparison sweep, a dashboard refresh) can skip the
    fold solves entirely. Entries hold ``(rdm, pair_values)`` tuples; the
    serving engine owns one instance and exposes ``hits`` in its stats
    (ROADMAP "RDM caching" item). Bounded LRU: RDMs are tiny (C², not N²),
    so an entry *count* cap is the right unit, unlike the byte-budgeted
    plan cache.
    """

    def __init__(self, max_entries: int = 256):
        if max_entries <= 0:
            raise ValueError("max_entries must be positive")
        self.max_entries = max_entries
        # Locked like PlanCache: thread-transport streams run on the
        # calling thread while the queue worker serves batches, so get/put
        # race without it.
        self._lock = threading.Lock()
        self._entries: "OrderedDict" = OrderedDict()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._entries

    def get(self, key):
        with self._lock:
            hit = self._entries.get(key)
            if hit is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return hit

    def put(self, key, value) -> None:
        with self._lock:
            self._entries[key] = value
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


def condition_pairs(num_classes: int) -> np.ndarray:
    """Static (B, 2) int32 array of condition pairs, B = C(C−1)/2.

    Row order is the upper-triangle order of ``np.triu_indices`` — the
    same order :func:`rdm_from_pair_values` scatters back from and
    ``repro.rsa.compare.upper_triangle`` vectorises RDMs into.
    """
    a, b = np.triu_indices(num_classes, 1)
    return np.stack([a, b], axis=1).astype(np.int32)


def pair_contrast_columns(y_cond: jax.Array, num_classes: int, dtype=float) -> jax.Array:
    """(N, B) matrix of ±1/0 pairwise contrast columns.

    Column j encodes pair (a, b) = ``condition_pairs(C)[j]``: +1 on
    samples of condition a, −1 on b, 0 elsewhere. These are exactly the
    label batch the serving engine's column path consumes.
    """
    oh = jax.nn.one_hot(y_cond, num_classes, dtype=dtype)  # (N, C)
    pairs = condition_pairs(num_classes)
    return oh[:, pairs[:, 0]] - oh[:, pairs[:, 1]]  # (N, B)


def pair_dissimilarities(
    plan: fastcv.CVPlan,
    cols: jax.Array,
    dissimilarity: str = "accuracy",
    adjust_bias: bool = True,
    fused: bool = False,
) -> jax.Array:
    """Per-column dissimilarity from one batched fold solve. cols: (N, B).

    The contrast columns double as test/train masks: ``cols[te_idx]`` is
    the ±1/0 test label of every (fold, sample, pair), so scoring needs no
    side-channel condition information — which is what lets padded
    (all-zero) columns pass through harmlessly in the serving engine.

    ``"accuracy"``: sign agreement of the bias-adjusted decision values
    with the ±1 labels, restricted to the pair's own test samples.
    ``"contrast"``: mean decision value over the pair's positive test
    samples minus the mean over its negative ones.
    """
    if dissimilarity not in _DISSIMILARITIES:
        raise ValueError(f"dissimilarity must be one of {_DISSIMILARITIES}")
    cols = cols.astype(plan.h.dtype)
    y_dot_te, y_dot_tr = fastcv.cv_errors(plan, cols, fused=fused)  # (K, m, B)
    te_lab = cols[plan.te_idx]  # (K, m, B)
    dv = y_dot_te
    if adjust_bias:
        if y_dot_tr is None:
            raise ValueError("plan must be prepared with with_train_block=True")
        tr_lab = cols[plan.tr_idx]  # (K, N-m, B)
        pos = (tr_lab > 0).astype(cols.dtype)
        neg = (tr_lab < 0).astype(cols.dtype)
        mu1 = jnp.sum(y_dot_tr * pos, axis=1) / jnp.maximum(jnp.sum(pos, axis=1), 1.0)  # (K, B)
        mu2 = jnp.sum(y_dot_tr * neg, axis=1) / jnp.maximum(jnp.sum(neg, axis=1), 1.0)
        dv = dv - 0.5 * (mu1 + mu2)[:, None, :]
    if dissimilarity == "accuracy":
        mask = (jnp.abs(te_lab) > 0).astype(cols.dtype)
        pred = jnp.where(dv >= 0, 1.0, -1.0).astype(cols.dtype)
        hit = jnp.where(mask > 0, (pred == te_lab).astype(cols.dtype), 0.0)
        return jnp.sum(hit, axis=(0, 1)) / jnp.maximum(jnp.sum(mask, axis=(0, 1)), 1.0)
    pos = (te_lab > 0).astype(cols.dtype)
    neg = (te_lab < 0).astype(cols.dtype)
    m_pos = jnp.sum(dv * pos, axis=(0, 1)) / jnp.maximum(jnp.sum(pos, axis=(0, 1)), 1.0)
    m_neg = jnp.sum(dv * neg, axis=(0, 1)) / jnp.maximum(jnp.sum(neg, axis=(0, 1)), 1.0)
    return m_pos - m_neg


def rdm_from_pair_values(values: jax.Array, num_classes: int) -> jax.Array:
    """Scatter (B,) pair values into a symmetric (C, C) RDM, zero diagonal."""
    pairs = condition_pairs(num_classes)
    rdm = jnp.zeros((num_classes, num_classes), values.dtype)
    rdm = rdm.at[pairs[:, 0], pairs[:, 1]].set(values)
    return rdm + rdm.T


def rdm_binary(
    x: jax.Array,
    y_cond: jax.Array,
    folds: Folds,
    num_classes: int,
    lam: float = 1.0,
    *,
    dissimilarity: str = "accuracy",
    adjust_bias: bool = True,
    mode: str = "auto",
    plan: Optional[fastcv.CVPlan] = None,
) -> jax.Array:
    """One-shot cross-validated pairwise-contrast RDM. Returns (C, C).

    Builds (or reuses) a single plan over all N samples and evaluates all
    C(C−1)/2 contrasts as one label batch — the serving engine does the
    same thing through its cached-plan, shape-bucketed path.
    """
    if plan is None:
        plan = fastcv.prepare(x, folds, lam, mode=mode, with_train_block=adjust_bias)
    cols = pair_contrast_columns(y_cond, num_classes, plan.h.dtype)
    vals = pair_dissimilarities(plan, cols, dissimilarity=dissimilarity, adjust_bias=adjust_bias)
    return rdm_from_pair_values(vals, num_classes)


# ---------------------------------------------------------------------------
# Multi-class (confusion) contrasts
# ---------------------------------------------------------------------------


def rdm_from_confusion(preds: jax.Array, y_te: jax.Array, num_classes: int) -> jax.Array:
    """Symmetrised confusion-dissimilarity RDM from CV predictions.

    d(a, b) = 1 − (p(pred=b | true=a) + p(pred=a | true=b)) / 2 for a ≠ b,
    0 on the diagonal. Conditions the classifier confuses often are
    representationally close.
    """
    conf = metrics.confusion_matrix(preds.reshape(-1), y_te.reshape(-1), num_classes).astype(float)
    rates = conf / jnp.maximum(jnp.sum(conf, axis=1, keepdims=True), 1.0)
    sim = 0.5 * (rates + rates.T)
    eye = jnp.eye(num_classes, dtype=bool)
    return jnp.where(eye, 0.0, 1.0 - sim)


def rdm_multiclass(plan: fastcv.CVPlan, y_cond: jax.Array, num_classes: int) -> jax.Array:
    """Confusion RDM from one Algorithm-2 multi-class CV run on the plan."""
    preds = multiclass.batch_predict(plan, y_cond[None, :], num_classes)[0]
    return rdm_from_confusion(preds, y_cond[plan.te_idx], num_classes)


# ---------------------------------------------------------------------------
# Non-cross-validated pattern RDMs (condition means / model-RDM building)
# ---------------------------------------------------------------------------


def condition_means(x: jax.Array, y_cond: jax.Array, num_classes: int) -> jax.Array:
    """(C, P) mean feature pattern per condition."""
    oh = jax.nn.one_hot(y_cond, num_classes, dtype=x.dtype)  # (N, C)
    counts = jnp.maximum(jnp.sum(oh, axis=0), 1.0)
    return jnp.matmul(oh.T, x, precision=dot_precision(x.dtype)) / counts[:, None]


def ring_rdm(num_classes: int, dtype=float) -> jax.Array:
    """(C, C) circular-distance model RDM: d(a, b) = min(|a−b|, C−|a−b|).

    The standard "ring" candidate structure for ordered condition sets
    (orientations, positions, phases) — used by the demos and benchmarks
    as a model-RDM everybody can construct without data.
    """
    idx = jnp.arange(num_classes)
    d = jnp.abs(idx[:, None] - idx[None, :])
    return jnp.minimum(d, num_classes - d).astype(dtype)


def euclidean_rdm(patterns: jax.Array, impl: str = "auto") -> jax.Array:
    """(C, C) squared-Euclidean RDM over row patterns.

    ``impl``: "auto" (Pallas ``pairdist`` kernel on TPU, plain XLA
    elsewhere), "pallas", or "xla" — the same dispatch convention as the
    serving engine's Gram builds.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        from repro.kernels.pairdist.ops import pairwise_sq_dists

        return pairwise_sq_dists(patterns)
    from repro.kernels.pairdist.ref import pairwise_sq_dists_ref

    return pairwise_sq_dists_ref(patterns)


# ---------------------------------------------------------------------------
# Searchlight sweeps: Q independent RDM problems over the mesh
# ---------------------------------------------------------------------------


def searchlight_rdm(
    xs: jax.Array,
    y_cond: jax.Array,
    folds: Folds,
    lam: float,
    mesh,
    *,
    num_classes: int,
    dissimilarity: str = "accuracy",
    adjust_bias: bool = True,
    mode: str = "auto",
    problem_axes: tuple = ("pod", "data"),
) -> jax.Array:
    """Per-searchlight RDMs: xs (Q, N, P_local) → (Q, C, C).

    Each problem builds its own plan and scores all pairwise contrasts
    locally; problems shard over the mesh's problem axes with zero
    cross-problem traffic (the ``core.distributed`` problem-axis
    decomposition, paper §4.2).
    """
    from repro.core.distributed import sharded_problems

    def one_problem(x, y_cond, te_idx, tr_idx):
        return rdm_binary(
            x,
            y_cond,
            Folds.with_indices(te_idx, tr_idx),
            num_classes,
            lam,
            dissimilarity=dissimilarity,
            adjust_bias=adjust_bias,
            mode=mode,
        )

    return sharded_problems(
        one_problem, xs, mesh, y_cond, folds.te_idx, folds.tr_idx, problem_axes=problem_axes
    )


# ---------------------------------------------------------------------------
# Serving support: fresh jitted evaluator for the engine's column path
# ---------------------------------------------------------------------------


def make_eval_pairs(
    dissimilarity: str = "accuracy", adjust_bias: bool = True,
    donate: bool = False, fused: bool = False
):
    """Fresh jitted evaluator ``(plan, cols (N, B)) -> (B,) dissimilarities``.

    Mirrors ``fastcv.make_eval_binary``: each call returns an
    independently-cached jit so the serve engine can count compiles via
    ``fn._cache_size()``; ``donate`` aliases the contrast batch on TPU/GPU,
    ``fused`` routes the fold solves through the Pallas kernels.
    """
    kw = {"donate_argnums": (1,)} if donate else {}
    return jax.jit(
        functools.partial(
            pair_dissimilarities, dissimilarity=dissimilarity,
            adjust_bias=adjust_bias, fused=fused
        ),
        **kw,
    )
