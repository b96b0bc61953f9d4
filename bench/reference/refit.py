"""The plain reference: refit every fold from scratch (the paper's "standard approach").

Copied from the repository's chip smoke and vectorised over label
columns; it imports nothing of the program. Ridge regression with an
unpenalised intercept, solved per fold in its N_tr × N_tr dual form, turns
into binary LDA (bias adjusted to the midpoint of the two classes' mean
training decision values) and into multi-class LDA in its optimal-scoring
form (Hastie et al. 1995; the C × C eigenproblem per fold and label vector).

One implementation serves two precisions. :data:`FLOAT64` is the reference
that decides ``correct``. :data:`ONE_PASS` is the control: the same
arithmetic in float32 with every contraction done as one bfloat16 pass
(operands rounded to bfloat16, products accumulated in float32), which is
what a TPU computes for a float32 matmul at its default precision, the
step a program tempted to drop the ``HIGHEST`` that the configurations
state would take. The rounding is spelled out, so the control computes the
same on any backend.

Everything runs on the host in NumPy, in blocks of label columns, after
the measured window has closed.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import ml_dtypes
import numpy as np

__all__ = [
    "FLOAT64",
    "ONE_PASS",
    "Precision",
    "binary_dvals",
    "fold_accuracy",
    "multiclass_predict",
    "permute",
]


def _dot64(a, b):
    return a @ b


def _bf16(a):
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def _dot_one_pass(a, b):
    """float32 product as one bfloat16 pass with float32 accumulation."""
    return _bf16(a) @ _bf16(b)


@dataclasses.dataclass(frozen=True)
class Precision:
    """Working dtype and contraction of one run of the reference."""

    name: str
    dtype: type
    dot: Callable


FLOAT64 = Precision("float64", np.float64, _dot64)
ONE_PASS = Precision("bf16_one_pass", np.float32, _dot_one_pass)


def permute(y: np.ndarray, perms: np.ndarray) -> np.ndarray:
    """(N, B) permuted label columns from (B, N) index rows."""
    return np.asarray(y)[np.asarray(perms)].T


class _Folds:
    """Per-fold dual-form ridge solves, factored once for many label columns."""

    def __init__(self, x, te, tr, lam, prec: Precision):
        self.prec = prec
        self.te, self.tr = np.asarray(te), np.asarray(tr)
        x = np.asarray(x, prec.dtype)
        self.folds = []
        for te_k, tr_k in zip(self.te, self.tr):
            mu = x[tr_k].mean(0)
            xc = x[tr_k] - mu
            g = prec.dot(xc, xc.T)
            k_te = prec.dot(x[te_k] - mu, xc.T)
            a = g + lam * np.eye(len(tr_k), dtype=prec.dtype)
            self.folds.append((g, k_te, a))

    def fits(self, y):
        """Ridge fits on the test rows (K, m, B) and training rows (K, N−m, B)."""
        y = np.asarray(y, self.prec.dtype)
        fit_te, fit_tr = [], []
        for (g, k_te, a), tr_k in zip(self.folds, self.tr):
            ybar = y[tr_k].mean(0)
            alpha = np.linalg.solve(a, y[tr_k] - ybar)
            fit_tr.append(self.prec.dot(g, alpha) + ybar)
            fit_te.append(self.prec.dot(k_te, alpha) + ybar)
        return np.stack(fit_te), np.stack(fit_tr)


def binary_dvals(x, y, te, tr, lam, prec: Precision = FLOAT64, block: int = 1024):
    """Bias-adjusted binary-LDA decision values (K, m, B) for ±1 columns y (N, B)."""
    folds = _Folds(x, te, tr, lam, prec)
    out = []
    for s in range(0, y.shape[1], block):
        yb = np.asarray(y[:, s:s + block], prec.dtype)
        fit_te, fit_tr = folds.fits(yb)
        y_tr = yb[folds.tr]  # (K, N−m, B)
        pos = (y_tr > 0).astype(prec.dtype)
        neg = (y_tr < 0).astype(prec.dtype)
        mu1 = (fit_tr * pos).sum(1) / pos.sum(1)
        mu2 = (fit_tr * neg).sum(1) / neg.sum(1)
        out.append(fit_te - 0.5 * (mu1 + mu2)[:, None, :])
    return np.concatenate(out, axis=-1)


def fold_accuracy(dv, y_te):
    """Binary accuracy over all test trials per column: dv, y_te (K, m, B) → (B,)."""
    return (np.where(dv >= 0, 1.0, -1.0) == np.sign(y_te)).mean(axis=(0, 1))


def multiclass_predict(x, labels, te, tr, lam, num_classes, prec: Precision = FLOAT64,
                       block: int = 256):
    """Multi-class LDA predictions and class distances per label row.

    labels: int (B, N). Returns (pred (B, K, m), dists (B, K, m, C)): the
    nearest class centroid in the discriminant space of optimal scoring,
    refit per fold (Hastie et al. 1995), and the squared distances.
    """
    folds = _Folds(x, te, tr, lam, prec)
    eye = np.eye(num_classes, dtype=prec.dtype)
    labels = np.asarray(labels)
    n_b, n = labels.shape
    preds, dists = [], []
    for s in range(0, n_b, block):
        lb = labels[s:s + block]
        b = lb.shape[0]
        y1h = eye[lb]  # (b, N, C)
        cols = np.transpose(y1h, (1, 0, 2)).reshape(n, b * num_classes)
        fit_te, fit_tr = folds.fits(cols)
        k, m = fit_te.shape[:2]
        fit_te = fit_te.reshape(k, m, b, num_classes).transpose(2, 0, 1, 3)
        fit_tr = fit_tr.reshape(k, -1, b, num_classes).transpose(2, 0, 1, 3)
        d = _optimal_scoring_dists(fit_te, fit_tr, y1h[:, folds.tr], prec)
        dists.append(d)
        preds.append(d.argmin(-1))
    return np.concatenate(preds), np.concatenate(dists)


def _optimal_scoring_dists(f_te, f_tr, yk, prec: Precision):
    """Squared centroid distances (B, K, m, C) from fits (B, K, ·, C), one-hot yk."""
    eps = 1e-10
    n_tr, c = yk.shape[-2:]
    counts = yk.sum(-2)  # (B, K, C)
    dm = 1.0 / np.sqrt(counts / n_tr)
    m = np.einsum("bknc,bknd->bkcd", f_tr, yk) / n_tr
    ms = dm[..., :, None] * m * dm[..., None, :]
    evals, evecs = np.linalg.eigh(0.5 * (ms + np.swapaxes(ms, -1, -2)))
    keep = np.arange(c - 2, -1, -1)  # drop the trivial α² = 1 pair
    a2 = np.clip(evals[..., keep], eps, 1.0 - eps)
    theta = dm[..., :, None] * evecs[..., keep] / (
        np.sqrt(n_tr) * np.sqrt(a2 * (1.0 - a2)))[..., None, :]
    s_te = np.einsum("bkmc,bkcd->bkmd", f_te, theta)
    s_tr = np.einsum("bknc,bkcd->bknd", f_tr, theta)
    centroids = np.einsum("bknc,bknd->bkcd", yk, s_tr) / counts[..., None]
    diff = s_te[..., :, None, :] - centroids[..., None, :, :]
    return (diff ** 2).sum(-1).astype(prec.dtype)
