"""Hyperparameter tuning with the analytical machinery (beyond the paper).

The hat matrix makes LOO cross-validation *algebraically free* per ridge
λ once the centered Gram is eigendecomposed:

    G_c = U diag(g) Uᵀ            (one O(N³) eigh)
    H(λ) = 1/N·11ᵀ + U diag(g/(g+λ)) Uᵀ       (O(N²) per λ)
    LOO:  ė_i = ê_i / (1 − H_ii(λ))            (Eq. 14 with m = 1)

so a whole λ grid costs little more than a single fit — the natural
companion to the paper's §2.6 recommendation to use ridge, removing the
one hyperparameter the analytical approach asks for. (The paper tunes
nothing; shrinkage practice uses Ledoit-Wolf — also available via
repro.core.shrinkage and convertible with Eq. 18.)
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.common import dot_precision

__all__ = ["RidgeTuneResult", "loo_curve", "tune_ridge"]


class RidgeTuneResult(NamedTuple):
    best_lambda: jax.Array      # ()
    best_score: jax.Array       # ()
    lambdas: jax.Array          # (L,)
    scores: jax.Array           # (L,) criterion per λ (lower is better)


def _centered(x: jax.Array) -> jax.Array:
    return x - jnp.mean(x, axis=0, keepdims=True)


def _eig_gram(x: jax.Array):
    xc = _centered(x)
    g = jnp.matmul(xc, xc.T, precision=dot_precision(x.dtype))
    evals, u = jnp.linalg.eigh(g)
    return jnp.maximum(evals, 0.0), u


def loo_curve(x: jax.Array, y: jax.Array, lambdas: jax.Array,
              criterion: str = "mse"):
    """LOO CV curve over a λ grid from one eigendecomposition.

    y: (N,) continuous response or ±1 labels. criterion: "mse" (squared
    LOO residual) or "error" (misclassification of sign(ẏ)).
    Returns (L,) scores, exact per Eq. 14 (m=1).
    """
    n = x.shape[0]
    y = y.astype(jnp.float64 if x.dtype == jnp.float64 else jnp.float32)
    evals, u = _eig_gram(x)
    dot = partial(jnp.matmul, precision=dot_precision(y.dtype))
    uy = dot(u.T, y)                               # (N,)

    def one_lambda(lam):
        w = evals / (evals + lam)                  # (N,) spectral filter
        # ŷ = H y = 1/N Σy + U diag(w) Uᵀ y
        y_hat = jnp.mean(y) + dot(u, w * uy)
        # H_ii = 1/N + Σ_k w_k U_ik²  ... plus cross term from 11ᵀ/N and
        # U diag(w) Uᵀ? The two parts are NOT orthogonal in general, but
        # H = 1/N·11ᵀ + U W Uᵀ exactly (DESIGN §2), so
        # H_ii = 1/N + Σ_k w_k U_ik² + 0 (the decomposition is additive).
        h_diag = 1.0 / n + jnp.sum(w[None, :] * u * u, axis=1)
        e_hat = y - y_hat
        e_loo = e_hat / jnp.maximum(1.0 - h_diag, 1e-12)
        if criterion == "error":
            y_loo = y - e_loo
            return jnp.mean((jnp.sign(y_loo) != jnp.sign(y)).astype(y.dtype))
        return jnp.mean(e_loo**2)

    return jax.vmap(one_lambda)(lambdas.astype(y.dtype))


def tune_ridge(x: jax.Array, y: jax.Array, lambdas=None,
               criterion: str = "mse") -> RidgeTuneResult:
    """Pick λ by exact LOO over a (default log-spaced) grid.

    Serving equivalent: ``Workload(kind="tune", x=x, y=y, ...)``."""
    if lambdas is None:
        scale = jnp.sum(jnp.square(_centered(x))) / x.shape[0]  # tr(G_c) / N
        lambdas = scale * jnp.logspace(-4, 2, 25)
    lambdas = jnp.asarray(lambdas)
    scores = loo_curve(x, y, lambdas, criterion=criterion)
    i = jnp.argmin(scores)
    return RidgeTuneResult(lambdas[i], scores[i], lambdas, scores)
