"""Simulated classification/regression data (paper §2.12).

"Each class centroid is randomly placed on the surface of a unit
hypersphere in feature space. A common covariance matrix is randomly
sampled from a Wishart distribution. Samples are then created by randomly
sampling from a multivariate normal distribution parameterised by the
corresponding class centroid and the common covariance matrix."
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["make_classification", "make_regression"]


def _wishart_cholesky(key: jax.Array, p: int, dof: int, dtype) -> jax.Array:
    """Cholesky factor of a Wishart(I, dof)/dof sample, via its Bartlett-free
    construction A = GᵀG/dof with G ~ N(0,1)^{dof×p} (dof >= p)."""
    g = jax.random.normal(key, (dof, p), dtype)
    a = g.T @ g / dof + 1e-6 * jnp.eye(p, dtype=dtype)
    return jnp.linalg.cholesky(a)


def make_classification(key: jax.Array, n: int, p: int, num_classes: int = 2,
                        dtype=float, class_sep: float = 1.0):
    """Paper §2.12 generator. Returns (x (N,P), y int (N,) in [0, C)).

    Equal class proportions; centroids uniform on the unit hypersphere
    scaled by ``class_sep``; shared Wishart covariance.
    """
    k_cent, k_wish, k_noise = jax.random.split(key, 3)
    cent = jax.random.normal(k_cent, (num_classes, p), dtype)
    cent = class_sep * cent / jnp.linalg.norm(cent, axis=1, keepdims=True)
    chol = _wishart_cholesky(k_wish, p, max(p, 2 * p), dtype)
    y = jnp.arange(n, dtype=jnp.int32) % num_classes
    z = jax.random.normal(k_noise, (n, p), dtype)
    x = cent[y] + z @ chol.T
    return x, y


def make_regression(key: jax.Array, n: int, p: int, noise: float = 0.1,
                    dtype=float):
    """Linear model y = Xw* + b* + ε for regression CV tests/benchmarks."""
    k_x, k_w, k_e = jax.random.split(key, 3)
    x = jax.random.normal(k_x, (n, p), dtype)
    w = jax.random.normal(k_w, (p,), dtype) / jnp.sqrt(p)
    y = x @ w + 0.5 + noise * jax.random.normal(k_e, (n,), dtype)
    return x, y
