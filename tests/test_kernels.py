"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

All kernels run in interpret mode on CPU (the kernels target TPU; interpret
executes the kernel bodies exactly). Tolerances: f64 near-exact; f32/bf16
allow accumulation-order noise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gram.ops import gram, centered_gram
from repro.kernels.gram.ref import gram_ref, centered_gram_ref
from repro.kernels.hat_apply.ops import hat_errors
from repro.kernels.hat_apply.ref import hat_apply_ref
from repro.kernels.foldsolve.ops import foldsolve, fold_jitter
from repro.kernels.foldsolve.ref import foldsolve_ref
from repro.kernels.fold_eval.ops import fold_eval
from repro.kernels.fold_eval.ref import fold_eval_np, fold_eval_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.pairdist.ops import pairwise_sq_dists
from repro.kernels.pairdist.ref import pairwise_sq_dists_ref

_TOL = {
    jnp.float64: dict(rtol=1e-9, atol=1e-9),
    jnp.float32: dict(rtol=2e-3, atol=2e-3),
}


def _key(i=0):
    return jax.random.PRNGKey(i)


# ---------------------------------------------------------------- gram ----

@pytest.mark.parametrize("n,p", [(8, 16), (100, 300), (256, 512), (130, 70),
                                 (33, 1000)])
@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
def test_gram_sweep(n, p, dtype):
    x = jax.random.normal(_key(n + p), (n, p), dtype)
    got = gram(x, interpret=True)
    want = gram_ref(x)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=_TOL[dtype]["rtol"],
                               atol=_TOL[dtype]["atol"] * scale)


def test_centered_gram():
    x = jax.random.normal(_key(3), (64, 200), jnp.float64)
    np.testing.assert_allclose(np.asarray(centered_gram(x, interpret=True)),
                               np.asarray(centered_gram_ref(x)), rtol=1e-9,
                               atol=1e-9)


def test_gram_block_shapes():
    x = jax.random.normal(_key(5), (96, 160), jnp.float64)
    want = gram_ref(x)
    for bn, bp in [(32, 32), (48, 80), (96, 160)]:
        got = gram(x, block_n=bn, block_p=bp, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-9, atol=1e-9)


# ------------------------------------------------------------ pairdist ----

@pytest.mark.parametrize("c,p", [(5, 30), (8, 128), (33, 500), (17, 1000)])
@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
def test_pairdist_sweep(c, p, dtype):
    u = jax.random.normal(_key(c * p), (c, p), dtype)
    got = pairwise_sq_dists(u, interpret=True)
    want = pairwise_sq_dists_ref(u)
    scale = float(jnp.max(jnp.abs(want))) + 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=_TOL[dtype]["rtol"],
                               atol=_TOL[dtype]["atol"] * scale)
    # the diagonal cancels ‖u‖² + ‖u‖² − 2u·u, so its absolute error scales
    # with the distance magnitudes (visible in f32)
    d = np.asarray(got)
    assert np.all(d >= 0.0)
    assert np.allclose(np.diag(d), 0.0, atol=_TOL[dtype]["atol"] * scale)


def test_pairdist_block_shapes():
    u = jax.random.normal(_key(6), (24, 160), jnp.float64)
    want = pairwise_sq_dists_ref(u)
    for bc, bp in [(8, 32), (24, 80), (24, 160)]:
        got = pairwise_sq_dists(u, block_c=bc, block_p=bp, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-9, atol=1e-9)


# ----------------------------------------------------------- hat_apply ----

@pytest.mark.parametrize("n,b", [(16, 1), (100, 7), (256, 128), (73, 33)])
@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
def test_hat_apply_sweep(n, b, dtype):
    h = jax.random.normal(_key(n), (n, n), dtype) / n
    y = jax.random.normal(_key(b + 1), (n, b), dtype)
    got = hat_errors(h, y, interpret=True)
    want = hat_apply_ref(h, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL[dtype])


def test_hat_apply_1d():
    h = jax.random.normal(_key(9), (50, 50), jnp.float64) / 50
    y = jax.random.normal(_key(10), (50,), jnp.float64)
    got = hat_errors(h, y, interpret=True)
    assert got.shape == (50,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(y - h @ y),
                               rtol=1e-10)


# ----------------------------------------------------------- foldsolve ----

@pytest.mark.parametrize("k,m,b", [(5, 8, 1), (10, 20, 4), (4, 50, 16),
                                   (2, 1, 3)])
@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
def test_foldsolve_sweep(k, m, b, dtype):
    key1, key2 = jax.random.split(_key(k * m))
    # realistic H_Te blocks: contraction-like, spectrum well inside (0,1)
    a = jax.random.normal(key1, (k, m, m), dtype) / (3.0 * m**0.5)
    h_te = jnp.einsum("kij,klj->kil", a, a)      # PSD, small norm
    e = jax.random.normal(key2, (k, m, b), dtype)
    got = foldsolve(h_te, e, interpret=True)
    want = foldsolve_ref(h_te, e)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-3 if dtype == jnp.float32 else 1e-8,
                               atol=5e-4 if dtype == jnp.float32 else 1e-9)


def test_foldsolve_2d_rhs():
    h_te = jnp.zeros((3, 4, 4), jnp.float64)
    e = jax.random.normal(_key(2), (3, 4), jnp.float64)
    got = foldsolve(h_te, e, interpret=True)     # (I-0)^{-1} e = e
    np.testing.assert_allclose(np.asarray(got), np.asarray(e), rtol=1e-12)


def test_foldsolve_matches_cv_plan_solves():
    """End-to-end: kernel solves == the fastcv cho_solve path on real H."""
    from repro.core import fastcv, folds as foldlib
    from repro.data import synthetic
    x, yc = synthetic.make_classification(_key(0), 40, 120)
    y = jnp.where(yc == 0, -1.0, 1.0)
    f = foldlib.kfold(40, 5, seed=1)
    plan = fastcv.prepare(x, f, 1.0, with_train_block=False)
    e_hat = y - plan.h @ y
    h_te = plan.h[f.te_idx[:, :, None], f.te_idx[:, None, :]]
    got = foldsolve(h_te, e_hat[f.te_idx], interpret=True)
    y_dot_te, _ = fastcv.cv_errors(plan, y)
    want = y[f.te_idx] - y_dot_te                 # ė_Te
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-8,
                               atol=1e-9)


# ----------------------------------------------------- flash attention ----

@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (6, 1)])
@pytest.mark.parametrize("s", [32, 128, 200])
def test_flash_causal_gqa_sweep(hq, hkv, s):
    b, d = 2, 16
    dtype = jnp.float32
    q = jax.random.normal(_key(1), (b, hq, s, d), dtype)
    k = jax.random.normal(_key(2), (b, hkv, s, d), dtype)
    v = jax.random.normal(_key(3), (b, hkv, s, d), dtype)
    scale = 1.0 / d**0.5
    got = flash_attention(q, k, v, scale=scale, block_q=64, block_k=64,
                          interpret=True)
    want = attention_ref(q, k, v, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("window", [16, 64])
def test_flash_local_window(window):
    b, h, s, d = 1, 2, 128, 8
    q = jax.random.normal(_key(4), (b, h, s, d), jnp.float32)
    k = jax.random.normal(_key(5), (b, h, s, d), jnp.float32)
    v = jax.random.normal(_key(6), (b, h, s, d), jnp.float32)
    got = flash_attention(q, k, v, scale=0.3, window=window, block_q=32,
                          block_k=32, interpret=True)
    want = attention_ref(q, k, v, scale=0.3, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)


def test_flash_softcap():
    b, h, s, d = 1, 2, 64, 8
    q = jax.random.normal(_key(7), (b, h, s, d), jnp.float32) * 3
    k = jax.random.normal(_key(8), (b, h, s, d), jnp.float32) * 3
    v = jax.random.normal(_key(9), (b, h, s, d), jnp.float32)
    got = flash_attention(q, k, v, scale=0.5, softcap=20.0, block_q=32,
                          block_k=32, interpret=True)
    want = attention_ref(q, k, v, scale=0.5, softcap=20.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)


def test_flash_bf16_io():
    b, h, s, d = 1, 2, 64, 16
    q = jax.random.normal(_key(10), (b, h, s, d)).astype(jnp.bfloat16)
    k = jax.random.normal(_key(11), (b, h, s, d)).astype(jnp.bfloat16)
    v = jax.random.normal(_key(12), (b, h, s, d)).astype(jnp.bfloat16)
    got = flash_attention(q, k, v, scale=0.25, block_q=32, block_k=32,
                          interpret=True)
    want = attention_ref(q, k, v, scale=0.25)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want, dtype=np.float32),
                               rtol=5e-2, atol=5e-2)


# ----------------------------------------------------------- fold_eval ----

def _fold_eval_problem(k, m, n, b, dtype, seed=0):
    """Realistic fused-eval inputs: PSD small-norm hat, random fold gathers."""
    k1, k2, k3 = jax.random.split(_key(seed + k * m + n + b), 3)
    a = jax.random.normal(k1, (n, n), dtype) / (3.0 * n**0.5)
    h = a @ a.T                                   # PSD, spectrum in (0, 1)
    te = jax.random.permutation(k2, n)[: k * m].reshape(k, m)
    h_rows = h[te]
    h_te = h[te[:, :, None], te[:, None, :]]
    y = jax.random.normal(k3, (n, b), dtype)
    return h_rows, h_te, y, y[te]


@pytest.mark.parametrize("k,m,n,b", [(4, 8, 40, 5), (3, 7, 33, 17),
                                     (5, 16, 80, 1), (2, 12, 50, 130)])
@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
def test_fold_eval_sweep(k, m, n, b, dtype):
    """Fused kernel vs jnp oracle and host-LAPACK ground truth.

    Shapes include ragged fold coverage (K·m < N) and B both smaller and
    larger than the batch block.
    """
    h_rows, h_te, y, y_te = _fold_eval_problem(k, m, n, b, dtype)
    got = fold_eval(h_rows, h_te, y, y_te, interpret=True)
    t_ref, _ = fold_eval_ref(h_rows, h_te, y, y_te)
    t_np, _ = fold_eval_np(h_rows, h_te, y, y_te)
    scale = 1.0 + float(np.max(np.abs(t_np)))
    tol = 1e-5 if dtype == jnp.float32 else 1e-10
    assert float(np.max(np.abs(np.asarray(got) - t_np))) / scale < tol
    np.testing.assert_allclose(np.asarray(got), np.asarray(t_ref),
                               rtol=5e-4 if dtype == jnp.float32 else 1e-9,
                               atol=tol * scale)


def test_fold_eval_block_shapes():
    """Grid tiling is numerically invisible, dividing blocks or not."""
    h_rows, h_te, y, y_te = _fold_eval_problem(3, 8, 48, 20, jnp.float64)
    t_np, _ = fold_eval_np(h_rows, h_te, y, y_te)
    for bn, bb in [(16, 8), (48, 32), (32, 16), (64, 128)]:
        got = fold_eval(h_rows, h_te, y, y_te, block_n=bn, block_b=bb,
                        interpret=True)
        np.testing.assert_allclose(np.asarray(got), t_np, rtol=1e-9,
                                   atol=1e-9)


def _near_singular_h_te(k, m, dtype, seed=7):
    """H_Te blocks making I − H_Te singular to machine precision."""
    q, _ = jnp.linalg.qr(jax.random.normal(_key(seed), (m, m), dtype))
    d = jnp.concatenate([jnp.ones((m - 1,), dtype), jnp.array([1e-14], dtype)])
    a = (q * d[None, :]) @ q.T                    # I − H_Te = Q diag(d) Qᵀ
    h_te = jnp.eye(m, dtype=dtype)[None] - a[None]
    return jnp.tile(h_te, (k, 1, 1))


def test_foldsolve_jitter_near_singular():
    """The docstring's λ→0 lifeline: the residual-checked retry keeps
    near-singular folds finite and matches the shifted LAPACK solve."""
    k, m, b = 3, 12, 4
    h_te = _near_singular_h_te(k, m, jnp.float64)
    e = jax.random.normal(_key(8), (k, m, b), jnp.float64)
    raw = foldsolve(h_te, e, interpret=True, jitter=None)
    got = foldsolve(h_te, e, interpret=True)
    assert bool(jnp.all(jnp.isfinite(got)))
    # the retry solves A + εI exactly: compare against LAPACK on the
    # shifted system (relative tolerance — solutions are O(1/ε)-large)
    eps = np.asarray(fold_jitter(h_te))
    eye = np.eye(m)
    want = np.stack([
        np.linalg.solve(eye - np.asarray(h_te[i]) + eps[i] * eye,
                        np.asarray(e[i])) for i in range(k)
    ])
    rel = np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want))
    assert rel < 1e-8
    # and the raw path really was pathological (else the test is vacuous)
    assert (not bool(jnp.all(jnp.isfinite(raw)))
            or float(jnp.max(jnp.abs(raw))) > 1e6 * np.max(np.abs(want)))


def test_foldsolve_jitter_noop_when_well_conditioned():
    """jitter="auto" must be bit-identical to jitter=None off the edge."""
    k1, k2 = jax.random.split(_key(13))
    a = jax.random.normal(k1, (4, 10, 10), jnp.float64) / 10.0
    h_te = jnp.einsum("kij,klj->kil", a, a)
    e = jax.random.normal(k2, (4, 10, 6), jnp.float64)
    np.testing.assert_array_equal(
        np.asarray(foldsolve(h_te, e, interpret=True)),
        np.asarray(foldsolve(h_te, e, interpret=True, jitter=None)))


def test_fold_eval_jitter_near_singular():
    """The fused wrapper ports the same retry: finite output matching the
    shifted solve, with ê_Te reused from the fused launch."""
    k, m, n, b = 2, 8, 32, 5
    h_rows, _, y, y_te = _fold_eval_problem(k, m, n, b, jnp.float64)
    h_te = _near_singular_h_te(k, m, jnp.float64)
    got = fold_eval(h_rows, h_te, y, y_te, interpret=True)
    assert bool(jnp.all(jnp.isfinite(got)))
    e = np.asarray(y_te) - np.einsum("kmn,nb->kmb", np.asarray(h_rows),
                                     np.asarray(y))
    eps = np.asarray(fold_jitter(h_te))
    eye = np.eye(m)
    want = np.stack([
        np.linalg.solve(eye - np.asarray(h_te[i]) + eps[i] * eye, e[i])
        for i in range(k)
    ])
    rel = np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want))
    assert rel < 1e-8


# ------------------------------------------------------- bf16_gram mode ----

def test_gram_bf16_precision_bound():
    """bf16_gram stays inside the documented ~2·2⁻⁸‖X_c‖² bound and the
    Pallas kernel matches the XLA fallback's numerics."""
    from repro.kernels.gram.ops import centered_gram_xla
    x = jax.random.normal(_key(21), (96, 300), jnp.float32)
    exact = np.asarray(centered_gram_ref(x))
    scale = float(np.max(np.abs(exact)))
    bound = 4.0 * 2.0**-8 * scale                 # 2× headroom on the bound
    for got in (gram(x, center=True, precision="bf16_gram", interpret=True),
                centered_gram_xla(x, precision="bf16_gram")):
        got = np.asarray(got)
        assert got.dtype == exact.dtype
        assert float(np.max(np.abs(got - exact))) < bound
    pallas = np.asarray(gram(x, center=True, precision="bf16_gram",
                             interpret=True))
    xla = np.asarray(centered_gram_xla(x, precision="bf16_gram"))
    np.testing.assert_allclose(pallas, xla, rtol=1e-6, atol=1e-6 * scale)


def test_gram_fp32_precision_is_default():
    x = jax.random.normal(_key(22), (32, 64), jnp.float64)
    np.testing.assert_array_equal(
        np.asarray(gram(x, center=True, interpret=True)),
        np.asarray(gram(x, center=True, precision="fp32", interpret=True)))
    with pytest.raises(ValueError, match="precision"):
        gram(x, precision="fp8")


# ------------------------------------------------------ device dtypes ----

_F64_CALLS = {
    "gram": lambda z: gram(z((16, 32)), interpret=False),
    "pairdist": lambda z: pairwise_sq_dists(z((4, 32)), interpret=False),
    "foldsolve": lambda z: foldsolve(z((2, 4, 4)), z((2, 4, 3)), interpret=False),
    "fold_eval": lambda z: fold_eval(z((2, 4, 8)), z((2, 4, 4)), z((8, 3)), z((2, 4, 3)),
                                     interpret=False),
}


@pytest.mark.parametrize("kernel", sorted(_F64_CALLS))
def test_compiled_kernel_rejects_float64(kernel):
    """The TPU has no float64: a compiled call names the problem instead of
    failing deep inside the kernel compiler."""
    with pytest.raises(TypeError, match=f"{kernel}: float64 operand"):
        _F64_CALLS[kernel](lambda shape: jnp.zeros(shape, jnp.float64))
