"""Jit'd public wrapper for foldsolve, with the λ→0 jitter fallback.

The kernel's pivot-free Gauss-Jordan is exact for the SPD, well-conditioned
A = I − H_Te that ridge-regularised plans produce (λ > 0 keeps H's spectrum
inside [0, 1)). As λ → 0 in the P ≥ N regime, H_Te → I and A degenerates;
the elimination then divides by vanishing pivots and the solve degrades or
overflows. The wrapper implements the fallback the kernel docstring
promises as a *residual-checked retry*: solve once, measure the per-fold
residual ‖A ė − ê‖_∞ against √ε·(1 + ‖ê‖_∞), and — only if some fold fails
(non-finite output counts as failing) — re-solve those folds against the
Tikhonov-shifted A + ε_k I with ε_k = :func:`fold_jitter`. The retry lives
under ``lax.cond``, so the healthy steady state pays one cheap residual
contraction and never re-enters the kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import check_device_dtypes, default_interpret, dot_precision
from repro.kernels.foldsolve.foldsolve import foldsolve_pallas

__all__ = ["foldsolve", "fold_jitter", "fold_residual_bad"]


def _residual_tol(dtype) -> float:
    """√ε acceptance threshold: far above a healthy solve's ~ε·m residual,
    far below the O(1) residual of a degenerate pivot-free elimination."""
    return float(jnp.finfo(dtype).eps) ** 0.5


def fold_jitter(h_te: jax.Array) -> jax.Array:
    """Per-fold Tikhonov shift ε_k = √ε·(1 + ‖I − H_Te[k]‖_max) — the
    jitter magnitude the retry applies (exposed so tests and callers can
    reproduce the shifted system exactly)."""
    m = h_te.shape[-1]
    eye = jnp.eye(m, dtype=h_te.dtype)
    a = eye[None] - h_te
    return _residual_tol(h_te.dtype) * (1.0 + jnp.max(jnp.abs(a), axis=(1, 2)))


def fold_residual_bad(h_te: jax.Array, t: jax.Array, e: jax.Array) -> jax.Array:
    """(K,) bool: folds whose solve t of (I − H_Te) t = e failed the
    residual check (or produced non-finite values)."""
    m = h_te.shape[-1]
    eye = jnp.eye(m, dtype=h_te.dtype)
    a = eye[None] - h_te
    r = jnp.einsum("kij,kjb->kib", a, t, precision=dot_precision(t.dtype)) - e
    scale = 1.0 + jnp.max(jnp.abs(e), axis=(1, 2))
    finite = jnp.all(jnp.isfinite(t), axis=(1, 2))
    # NaN propagates through max as NaN; comparisons with NaN are False,
    # so the finiteness term (not the residual term) must catch that case.
    resid_ok = jnp.max(jnp.abs(r), axis=(1, 2)) <= _residual_tol(e.dtype) * scale
    return ~(finite & resid_ok)


@functools.partial(jax.jit, static_argnames=("interpret", "jitter"))
def foldsolve(h_te: jax.Array, e_te: jax.Array, *,
              interpret: Optional[bool] = None,
              jitter: Optional[str] = "auto") -> jax.Array:
    """ė_Te = (I − H_Te)⁻¹ ê_Te for all folds at once.

    h_te: (K, m, m) diagonal fold blocks of the hat matrix.
    e_te: (K, m) or (K, m, B) full-fit errors (B = permutation batch).
    jitter: "auto" (default) enables the residual-checked retry against
        the shifted A + ε_k I for folds where the pivot-free elimination
        degrades (λ→0 edge cases); None disables it (raw kernel output).
    """
    if interpret is None:
        interpret = default_interpret()
    check_device_dtypes("foldsolve", interpret, h_te, e_te)
    squeeze = e_te.ndim == 2
    e = e_te[..., None] if squeeze else e_te
    out = foldsolve_pallas(h_te, e, interpret=interpret)
    if jitter == "auto":
        bad = fold_residual_bad(h_te, out, e)
        m = h_te.shape[-1]
        eye = jnp.eye(m, dtype=h_te.dtype)
        shift = jnp.where(bad, fold_jitter(h_te), 0.0)

        def _retry(_):
            # I − (H_Te − ε_k I) = A + ε_k I: the shift folds into h_te,
            # so the retry reuses the unmodified kernel.
            return foldsolve_pallas(
                h_te - shift[:, None, None] * eye[None], e, interpret=interpret
            )

        out = jax.lax.cond(jnp.any(bad), _retry, lambda _: out, None)
    elif jitter is not None:
        raise ValueError(f"jitter must be 'auto' or None, got {jitter!r}")
    return out[..., 0] if squeeze else out
