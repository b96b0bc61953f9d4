"""Jit'd public wrapper for the fused fold_eval kernel.

Pads the contraction (N) and batch (B) axes to block multiples — zero
padding is exact here: padded hat-row columns are zero, so padded y rows
contribute nothing to the contraction, and padded y_te columns produce
ê = 0 → ė = 0 blocks that are sliced away. Carries the same
residual-checked jitter fallback as ``foldsolve`` (see
:mod:`repro.kernels.foldsolve.ops`): the fused kernel also returns the
ê_Te block it solved against, so the residual check needs no
re-materialisation, and a failing fold re-solves only the (cheap,
standalone) fold-solve stage against the Tikhonov-shifted system — the
hat-row contraction is never repeated.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import check_device_dtypes, default_interpret, pad_to
from repro.kernels.fold_eval.fold_eval import (
    DEFAULT_BLOCK_B,
    DEFAULT_BLOCK_N,
    fold_eval_pallas,
)
from repro.kernels.foldsolve.foldsolve import foldsolve_pallas
from repro.kernels.foldsolve.ops import fold_jitter, fold_residual_bad

__all__ = ["fold_eval"]


def _block(requested: Optional[int], default: int, dim: int) -> int:
    """Shrink the block to the padded-pow2 of a small dim (same idiom as
    gram/hat_apply: avoids padding a dim far past its size)."""
    return min(requested or default, max(8, 1 << (dim - 1).bit_length()))


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_b", "interpret", "jitter")
)
def fold_eval(h_rows: jax.Array, h_te: jax.Array, y: jax.Array,
              y_te: jax.Array, *, block_n: Optional[int] = None,
              block_b: Optional[int] = None,
              interpret: Optional[bool] = None,
              jitter: Optional[str] = "auto") -> jax.Array:
    """Fused ė_Te = (I − H_Te)⁻¹ (y_Te − H·y) for all folds in one launch.

    h_rows: (K, m, N) per-fold hat rows H[te_k, :].
    h_te:   (K, m, m) diagonal fold blocks H_Te.
    y:      (N, B) label batch.   y_te: (K, m, B) gathered test labels.
    Returns ė_Te of shape (K, m, B).

    jitter: "auto" (default) enables the residual-checked retry for λ→0
        edge cases; None disables it. The retry re-solves failing folds
        with the standalone foldsolve kernel against A + ε_k I
        (ε_k = :func:`repro.kernels.foldsolve.ops.fold_jitter`), reusing
        the fused kernel's ê_Te output as the RHS.
    """
    if interpret is None:
        interpret = default_interpret()
    check_device_dtypes("fold_eval", interpret, h_rows, h_te, y, y_te)
    k, m, n = h_rows.shape
    b = y.shape[1]
    bn = _block(block_n, DEFAULT_BLOCK_N, n)
    bb = _block(block_b, DEFAULT_BLOCK_B, b)

    h_rows_p = pad_to(h_rows, bn, axis=2)
    y_p = pad_to(pad_to(y, bn, axis=0), bb, axis=1)
    y_te_p = pad_to(y_te, bb, axis=2)

    t_p, e_p = fold_eval_pallas(h_rows_p, h_te, y_p, y_te_p,
                                block_n=bn, block_b=bb, interpret=interpret)
    t, e = t_p[:, :, :b], e_p[:, :, :b]

    if jitter == "auto":
        bad = fold_residual_bad(h_te, t, e)
        eye = jnp.eye(m, dtype=h_te.dtype)
        shift = jnp.where(bad, fold_jitter(h_te), 0.0)

        def _retry(_):
            # Only the solve stage re-runs (against the already-computed
            # ê_Te); I − (H_Te − ε_k I) = A + ε_k I folds the shift into
            # h_te, so the standalone kernel is reused unmodified.
            out = foldsolve_pallas(
                h_te - shift[:, None, None] * eye[None],
                pad_to(e, bb, axis=2), interpret=interpret,
            )
            return out[:, :, :b]

        t = jax.lax.cond(jnp.any(bad), _retry, lambda _: t, None)
    elif jitter is not None:
        raise ValueError(f"jitter must be 'auto' or None, got {jitter!r}")
    return t
