"""Multi-class LDA: direct form, optimal scoring, and analytical CV.

Implements the paper's novel extension (§2.8-2.10, Algorithm 2):

Step 1  Multivariate ridge regression of the class-indicator matrix Y on X̃.
        Cross-validated *exactly* via the hat-matrix identities (Eq. 14/15),
        column-wise over classes — shares ``repro.core.fastcv``.
Step 2  Optimal scores from the C×C eigenproblem of M = Ẏ_Trᵀ Y_Tr / N_Tr.
        We solve the *generalised* problem  M θ = α² D_π θ  with
        D_π = Y_Trᵀ Y_Tr / N_Tr (Hastie et al. 1995 constraint
        N⁻¹‖Yθ‖² = 1): whitening by D_π^{-1/2} turns it into a symmetric
        ``eigh`` — M is symmetric by construction (M = Y_Trᵀ X̃_Tr S_Tr
        X̃_Trᵀ Y_Tr / N_Tr), so this is exact, TPU-friendly (no
        non-symmetric ``eig``), and the trivial pair (α² = 1, θ = 1_C)
        is exact and unambiguous to drop.
Scaling W = B Θ D with D = N^{-1/2} diag(α²(1−α²))^{-1/2} (paper §2.9,
including the √N covariance-vs-scatter correction).

Classification is nearest-centroid in discriminant space; the intercept
column of X̃ shifts all scores and centroids equally, so distances (and
hence predictions) are unaffected (paper §2.10).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.scipy.linalg import cho_factor, cho_solve, solve_triangular

from repro.core import fastcv
from repro.core.folds import Folds
from repro.kernels.common import dot_precision

__all__ = [
    "onehot",
    "MulticlassLDA",
    "fit_multiclass",
    "predict_multiclass",
    "optimal_scoring_fit",
    "standard_cv_multiclass",
    "analytical_cv_multiclass",
    "batch_predict",
    "make_eval_multiclass",
]

_EPS = 1e-10


def onehot(y: jax.Array, num_classes: int, dtype=float) -> jax.Array:
    return jax.nn.one_hot(y, num_classes, dtype=dtype)


# ---------------------------------------------------------------------------
# Direct multi-class LDA (the paper's standard-approach comparator, §2.8)
# ---------------------------------------------------------------------------


class MulticlassLDA(NamedTuple):
    w: jax.Array          # (P, C-1) discriminant coordinates, Wᵀ(S_w+λI)W = I
    centroids: jax.Array  # (C, C-1) projected class means


def _scatter_matrices(x: jax.Array, y1h: jax.Array):
    """S_w, S_b and class means from one-hot labels (Eq. in §2.8)."""
    counts = jnp.sum(y1h, axis=0)                       # (C,)
    n = x.shape[0]
    m = (y1h.T @ x) / jnp.maximum(counts, 1.0)[:, None]  # (C, P) class means
    mbar = jnp.sum(counts[:, None] * m, axis=0) / n      # (P,) sample mean
    st = x.T @ x                                         # total raw scatter
    sw = st - (m * counts[:, None]).T @ m                # within-class
    mc = m - mbar[None, :]
    sb = (mc * counts[:, None]).T @ mc                   # between-classes
    return sw, sb, m, counts


def fit_multiclass(x: jax.Array, y1h: jax.Array, lam: float = 0.0) -> MulticlassLDA:
    """Generalised eigenproblem S_b W = (S_w + λI) W Λ via Cholesky whitening."""
    c = y1h.shape[1]
    p = x.shape[1]
    sw, sb, m, _ = _scatter_matrices(x, y1h)
    swr = sw + jnp.asarray(lam, x.dtype) * jnp.eye(p, dtype=x.dtype)
    l = jnp.linalg.cholesky(swr)
    a = solve_triangular(l, sb, lower=True)
    a = solve_triangular(l, a.T, lower=True)             # L⁻¹ S_b L⁻ᵀ
    a = 0.5 * (a + a.T)
    _, vecs = jnp.linalg.eigh(a)                         # ascending
    top = vecs[:, ::-1][:, : c - 1]                      # top C-1, descending
    w = solve_triangular(l.T, top, lower=False)          # W = L⁻ᵀ U
    centroids = m @ w
    return MulticlassLDA(w, centroids)


def predict_multiclass(x: jax.Array, model: MulticlassLDA) -> jax.Array:
    """Nearest-centroid classification in discriminant space."""
    scores = x @ model.w                                 # (N, C-1)
    d2 = jnp.sum((scores[:, None, :] - model.centroids[None]) ** 2, axis=-1)
    return jnp.argmin(d2, axis=-1)


# ---------------------------------------------------------------------------
# Optimal scoring (full-data fit; Hastie et al. 1995, paper §2.9)
# ---------------------------------------------------------------------------


def _os_step2(m: jax.Array, d_pi: jax.Array, n_tr):
    """Solve M θ = α² D_π θ; drop the trivial pair; return Θ·D (C, C-1).

    m:    (C, C) Ẏ_Trᵀ Y_Tr / N_Tr (symmetric up to float noise)
    d_pi: (C,)   class proportions of the training fold
    """
    c = m.shape[0]
    dm = 1.0 / jnp.sqrt(jnp.maximum(d_pi, _EPS))
    ms = dm[:, None] * m * dm[None, :]
    ms = 0.5 * (ms + ms.T)
    evals, evecs = jnp.linalg.eigh(ms)                   # ascending; trivial α²=1 last
    keep = jnp.arange(c - 2, -1, -1)                     # descending, drop last
    a2 = jnp.clip(evals[keep], _EPS, 1.0 - _EPS)
    theta = dm[:, None] * evecs[:, keep]                 # (C, C-1), θᵀD_πθ = I
    d = 1.0 / (jnp.sqrt(jnp.asarray(n_tr, m.dtype)) * jnp.sqrt(a2 * (1.0 - a2)))
    return theta * d[None, :], a2


def optimal_scoring_fit(x: jax.Array, y1h: jax.Array, lam: float = 0.0):
    """Full-data optimal scoring. Returns (w_os, scores_fn_weights):
    w_os (P, C-1) equals the direct-LDA W up to per-column sign."""
    n, p = x.shape
    xa = jnp.concatenate([x, jnp.ones((n, 1), x.dtype)], axis=1)
    i0 = jnp.eye(p + 1, dtype=x.dtype).at[p, p].set(0.0)
    a = xa.T @ xa + jnp.asarray(lam, x.dtype) * i0
    b = cho_solve(cho_factor(a), xa.T @ y1h)             # (P+1, C)
    y_fit = xa @ b                                       # Ŷ = HY
    m = y_fit.T @ y1h / n
    d_pi = jnp.sum(y1h, axis=0) / n
    theta_d, a2 = _os_step2(m, d_pi, n)
    w_os = b[:-1] @ theta_d                              # B Θ D  (bias row dropped)
    return w_os, a2


# ---------------------------------------------------------------------------
# Standard approach: retrain direct LDA on every fold (O(KNP² + KP³))
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("num_classes",))
def _standard_cv_multiclass_jit(x, y, te_idx, tr_idx, lam, num_classes):
    y1h = onehot(y, num_classes, dtype=x.dtype)

    def one_fold(idx_pair):
        te, tr = idx_pair
        model = fit_multiclass(x[tr], y1h[tr], lam)
        return predict_multiclass(x[te], model)

    preds = jax.lax.map(one_fold, (te_idx, tr_idx))
    return preds, y[te_idx]


def standard_cv_multiclass(x: jax.Array, y: jax.Array, folds: Folds,
                           num_classes: int, lam: float = 0.0):
    """Retrain-per-fold direct multi-class LDA. Returns (pred (K,m), y_te)."""
    return _standard_cv_multiclass_jit(x, y, folds.te_idx, folds.tr_idx,
                                       jnp.asarray(lam, x.dtype), num_classes)


# ---------------------------------------------------------------------------
# Analytical approach (Algorithm 2)
# ---------------------------------------------------------------------------


def _fold_predict(y_dot_te, y_dot_tr, y1h_tr, dtype):
    """Step 2 + nearest centroid for one fold (vmapped over folds/perms).

    y_dot_te: (m, C) CV regression fits on the test fold
    y_dot_tr: (N-m, C) CV regression fits on the training fold
    y1h_tr:   (N-m, C) one-hot training labels
    """
    n_tr = y1h_tr.shape[0]
    counts = jnp.sum(y1h_tr, axis=0)
    dot = partial(jnp.matmul, precision=dot_precision(dtype))
    m_mat = dot(y_dot_tr.T, y1h_tr) / n_tr               # Ẏ_Trᵀ Y_Tr / N_Tr
    theta_d, _ = _os_step2(m_mat, counts / n_tr, n_tr)
    scores_te = dot(y_dot_te, theta_d)                   # (m, C-1)
    scores_tr = dot(y_dot_tr, theta_d)                   # (N-m, C-1)
    centroids = dot(y1h_tr.T, scores_tr) / jnp.maximum(counts, 1.0)[:, None]
    d2 = jnp.sum((scores_te[:, None, :] - centroids[None]) ** 2, axis=-1)
    return jnp.argmin(d2, axis=-1)


def analytical_cv_multiclass(x: jax.Array, y: jax.Array, folds: Folds,
                             num_classes: int, lam: float = 0.0,
                             mode: str = "auto",
                             plan: fastcv.CVPlan | None = None):
    """Algorithm 2: exact CV for multi-class LDA from one full-data fit.

    Returns (pred (K, m), y_te (K, m)). Serving equivalent (bit-identical,
    plan-cached): ``Workload(kind="cv", estimator="multiclass", ...)``
    via ``repro.serve``.
    """
    if plan is None:
        plan = fastcv.prepare(x, folds, lam, mode=mode, with_train_block=True)
    y1h = onehot(y, num_classes, dtype=plan.h.dtype)
    y_dot_te, y_dot_tr = fastcv.cv_errors(plan, y1h)     # (K, m, C), (K, N-m, C)
    y1h_tr = y1h[plan.tr_idx]                            # (K, N-m, C)
    preds = jax.vmap(_fold_predict, in_axes=(0, 0, 0, None))(
        y_dot_te, y_dot_tr, y1h_tr, plan.h.dtype
    )
    return preds, y[plan.te_idx]


def batch_predict(plan: fastcv.CVPlan, y_batch: jax.Array,
                  num_classes: int, *, fused: bool = False) -> jax.Array:
    """Algorithm 2 for a batch of label vectors sharing one plan.

    ``y_batch``: int (B, N) — e.g. permutations or many client requests.
    Returns int predictions (B, K, m); step 1 reuses the plan's cached
    factorisations, step 2's C×C eigh is vmapped over (B × K).

    ``fused=True`` routes step 1 through the Pallas solve kernel — and,
    rather than vmapping a kernel launch per label vector, flattens the
    whole batch into one (N, B·C) column block so all B·C indicator
    columns share a single launch (multiclass plans carry train blocks,
    so this is the solve-stage fusion of ``fastcv.cv_errors_fused``).
    """
    dtype = plan.h.dtype
    if fused:
        bsz, n = y_batch.shape
        y1h = onehot(y_batch, num_classes, dtype=dtype)       # (B, N, C)
        cols = jnp.transpose(y1h, (1, 0, 2)).reshape(n, bsz * num_classes)
        y_dot_te, y_dot_tr = fastcv.cv_errors(plan, cols, fused=True)
        k, m = y_dot_te.shape[:2]
        y_dot_te = y_dot_te.reshape(k, m, bsz, num_classes)
        y_dot_tr = y_dot_tr.reshape(k, y_dot_tr.shape[1], bsz, num_classes)
        y1h_tr = y1h[:, plan.tr_idx]                          # (B, K, N-m, C)
        per_b = jax.vmap(_fold_predict, in_axes=(0, 0, 0, None))
        return jax.vmap(per_b, in_axes=(2, 2, 0, None))(
            y_dot_te, y_dot_tr, y1h_tr, dtype)

    def one(yb):
        y1h = onehot(yb, num_classes, dtype=dtype)
        y_dot_te, y_dot_tr = fastcv.cv_errors(plan, y1h)
        y1h_tr = y1h[plan.tr_idx]
        return jax.vmap(_fold_predict, in_axes=(0, 0, 0, None))(
            y_dot_te, y_dot_tr, y1h_tr, dtype)

    return jax.vmap(one)(y_batch)


def make_eval_multiclass(num_classes: int, donate: bool = False,
                         fused: bool = False):
    """Fresh jitted evaluator ``(plan, y (B, N) int) -> preds (B, K, m)``
    for the serve engine; ``donate`` aliases the label batch on TPU/GPU,
    ``fused`` routes the fold solves through the Pallas kernels."""
    kw = {"donate_argnums": (1,)} if donate else {}
    return jax.jit(
        lambda plan, y: batch_predict(plan, y, num_classes, fused=fused),
        **kw)
