"""The batched Jacobi eigensolver of multi-class step 2, and its counter.

``multiclass.jacobi_eigh`` replaces ``jnp.linalg.eigh`` for the C×C
optimal-scoring eigenproblems when C ≤ ``JACOBI_MAX_C``. It has to keep
``eigh``'s contract: eigenvalues ascending and within a few ulps·‖A‖,
orthonormal eigenvector columns in matching order, V diag(w) Vᵀ = A.
The reference is NumPy's float64 ``eigh``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import folds as foldlib, multiclass
from repro.data import synthetic
from repro.serve import CVEngine, EngineConfig

BATCH = 256
SPECTRA = ("generic", "near_one", "repeated", "diagonal", "zero")

# one compiled program per (C, dtype), shared by every spectrum
_batched_eigh = jax.jit(jax.vmap(multiclass.jacobi_eigh))


def _matrices(c: int, spectrum: str, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if spectrum == "zero":
        return np.zeros((BATCH, c, c))
    if spectrum == "diagonal":
        return np.stack([np.diag(d) for d in rng.standard_normal((BATCH, c))])
    if spectrum == "generic":
        w = rng.standard_normal((BATCH, c))
    elif spectrum == "near_one":  # the P >> N null: M ≈ I
        w = 1.0 - 10.0 ** rng.uniform(-5, -1, (BATCH, c))
    else:  # "repeated": values from {s, s+1, s+2}, the first one twice
        w = rng.integers(0, 3, (BATCH, c)).astype(float) + rng.standard_normal((BATCH, 1))
        w[:, -1] = w[:, 0]
    q, _ = np.linalg.qr(rng.standard_normal((BATCH, c, c)))
    a = np.einsum("bij,bj,bkj->bik", q, w, q)
    return 0.5 * (a + np.swapaxes(a, 1, 2))


@pytest.mark.parametrize("spectrum", SPECTRA)
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("c", [2, 3, 4, 5, 8])
def test_jacobi_eigh_matches_float64_eigh(c, dtype, spectrum):
    a = _matrices(c, spectrum, seed=c).astype(dtype)
    w, v = _batched_eigh(jnp.asarray(a))
    assert w.dtype == v.dtype == dtype
    w, v = np.asarray(w, np.float64), np.asarray(v, np.float64)
    a64 = a.astype(np.float64)
    ref = np.linalg.eigvalsh(a64)
    norm = np.linalg.norm(a64, 2, axis=(1, 2))[:, None]
    tol = 4 * c * np.finfo(dtype).eps * norm  # a few ulps·‖A‖
    assert np.all(np.diff(w, axis=1) >= 0), "eigenvalues not ascending"
    assert np.all(np.abs(w - ref) <= tol)
    orth = np.einsum("bji,bjk->bik", v, v) - np.eye(c)
    assert np.max(np.abs(orth)) <= 4 * c * np.finfo(dtype).eps
    recon = np.einsum("bij,bj,bkj->bik", v, w, v) - a64
    assert np.all(np.max(np.abs(recon), axis=2) <= tol)
    if spectrum in ("zero", "diagonal"):  # nothing to rotate: exact
        np.testing.assert_array_equal(w, np.sort(np.diagonal(a64, axis1=1, axis2=2), axis=1))
        np.testing.assert_array_equal(np.abs(v).sum(axis=1), np.ones((BATCH, c)))


def test_step2_solver_bound():
    """C up to the bound takes the Jacobi route; above it step 2 still
    lowers to the ``eigh`` primitive."""
    bound = multiclass.JACOBI_MAX_C
    assert multiclass.step2_solver(3) == multiclass.step2_solver(bound) == "jacobi"
    assert multiclass.step2_solver(bound + 1) == "eigh"

    def jaxpr(c):
        m = jnp.eye(c) * 0.5 + 0.1
        return str(jax.make_jaxpr(multiclass._os_step2)(m, jnp.full((c,), 1.0 / c), 40))

    assert "eigh" not in jaxpr(3)
    assert "eigh" in jaxpr(bound + 1)


def test_os_step2_same_on_both_routes(monkeypatch):
    """Step 2's scaled optimal scores Θ·D and α² agree between the Jacobi
    route and ``eigh`` (each column up to its sign)."""
    n, c = 90, 4
    x, y = synthetic.make_classification(jax.random.PRNGKey(3), n, 12, c, class_sep=1.5)
    y1h = multiclass.onehot(y, c)
    xa = jnp.concatenate([x, jnp.ones((n, 1))], axis=1)
    m = (xa @ jnp.linalg.lstsq(xa, y1h)[0]).T @ y1h / n
    d_pi = jnp.sum(y1h, axis=0) / n
    theta_d, a2 = multiclass._os_step2(m, d_pi, n)
    monkeypatch.setattr(multiclass, "JACOBI_MAX_C", 0)
    ref_theta_d, ref_a2 = multiclass._os_step2(m, d_pi, n)
    np.testing.assert_allclose(np.asarray(a2), np.asarray(ref_a2), rtol=1e-12)
    got, ref = np.asarray(theta_d), np.asarray(ref_theta_d)
    got = got * np.sign(np.sum(got * ref, axis=0))
    np.testing.assert_allclose(got, ref, rtol=1e-9, atol=1e-12)


@pytest.mark.parametrize("c", [3, multiclass.JACOBI_MAX_C + 1])
def test_step2_solves_counted_per_padded_row_and_fold(c):
    """One multi-class permutation test, then one cv eval of 3 label
    vectors, count padded B × K solves under the solver their C takes
    (observed: one row; null: 5 draws pad to 8; the eval: 3 rows to 4)."""
    n, p, k, n_perm = 8 * c, 6, 4, 5
    x, y = synthetic.make_classification(jax.random.PRNGKey(c), n, p, c, class_sep=2.0)
    engine = CVEngine(EngineConfig(cache_bytes=64 << 20))
    folds = foldlib.stratified_kfold(np.asarray(y), k, seed=0)
    _, plan = engine.plan(x, folds, 1.0)
    counter = engine.metrics.get("step2_solves_total")
    solver = multiclass.step2_solver(c)
    other = "eigh" if solver == "jacobi" else "jacobi"
    engine.permutation_multiclass(plan, y, n_perm, jax.random.PRNGKey(1), num_classes=c)
    assert counter.value(solver=solver) == (1 + 8) * k
    engine.eval_multiclass(plan, jnp.stack([y, y, y]), c)
    assert counter.value(solver=solver) == (1 + 8 + 4) * k
    assert counter.value(solver=other) == 0
