"""Jit'd public wrapper for the gram kernel: padding, centering, dispatch.

Also home of the ``precision="bf16_gram"`` mixed-precision build: the
O(N²P) Gram product — the only dimension-P contraction in the dual path —
is computed from a bf16 cast of the *centered* design with float32
accumulation (Pallas kernel and XLA fallback alike), then cast back to the
working dtype; every downstream solve stays full precision. Following the
blocked mixed-precision error analysis of Higham & Mary (2019), the
elementwise bf16 rounding of X_c bounds the Gram's relative error by
~2·2⁻⁸ ‖X_c‖² (bf16 has an 8-bit significand; the f32 accumulator
contributes O(P·2⁻²⁴), negligible), which the λ-regularised fold solves
damp rather than amplify — the documented bound the error tests pin.
Centering happens *before* the cast: means are O(‖X‖) quantities whose
bf16 rounding would otherwise leak a rank-1 error of the same order as
the signal.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import check_device_dtypes, default_interpret, dot_precision, pad_to
from repro.kernels.gram.gram import gram_pallas

__all__ = ["gram", "centered_gram", "centered_gram_xla", "check_precision",
           "PRECISIONS"]

#: Gram/hat build precisions: "fp32" = full-precision contractions in the
#: working dtype (float32 on the chip, float64 in the x64 test suite),
#: "bf16_gram" = bf16 inputs + f32 accumulation for the Gram product only.
PRECISIONS = ("fp32", "bf16_gram")


def check_precision(precision: Optional[str]) -> str:
    """Normalise (None → "fp32") and validate a precision name."""
    precision = precision or "fp32"
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, "
                         f"got {precision!r}")
    return precision


@functools.partial(jax.jit, static_argnames=(
    "block_n", "block_p", "interpret", "center", "precision"))
def gram(x: jax.Array, *, center: bool = False, block_n: Optional[int] = None,
         block_p: Optional[int] = None, interpret: Optional[bool] = None,
         precision: Optional[str] = None) -> jax.Array:
    """G = X Xᵀ (optionally column-centered first) via the Pallas kernel.

    Inputs of arbitrary (N, P) are zero-padded to block multiples; padding
    rows are sliced away on return (zero-padding P contributes 0 to XXᵀ).
    Blocks shrink to the (padded) matrix size for small problems.
    ``precision="bf16_gram"`` casts the (centered) input to bf16 for the
    contraction — the kernel accumulates in f32 — and returns the result
    in the input dtype (see module docstring for the error bound).
    """
    if interpret is None:
        interpret = default_interpret()
    check_device_dtypes("gram", interpret, x)
    precision = check_precision(precision)
    if center:
        x = x - jnp.mean(x, axis=0, keepdims=True)
    out_dtype = x.dtype
    if precision == "bf16_gram":
        x = x.astype(jnp.bfloat16)
    n, p = x.shape
    bn = min(block_n or 256, max(8, 1 << (n - 1).bit_length()))
    bp = min(block_p or 512, max(8, 1 << (p - 1).bit_length()))
    xp = pad_to(pad_to(x, bn, 0), bp, 1)
    g = gram_pallas(xp, block_n=bn, block_p=bp, interpret=interpret)
    return g[:n, :n].astype(out_dtype)


def centered_gram(x: jax.Array, **kw) -> jax.Array:
    """Centered Gram G_c = X_c X_cᵀ — the dual hat-matrix building block."""
    return gram(x, center=True, **kw)


def centered_gram_xla(x: jax.Array, *,
                      precision: Optional[str] = None) -> jax.Array:
    """Centered Gram on the plain XLA path (no Pallas launch).

    The fallback ``fastcv.prepare`` uses when no precomputed Gram is
    supplied: at ``precision="bf16_gram"`` the centered design is cast to
    bf16 and contracted with a float32 accumulator
    (``preferred_element_type``) — the same numerics as the Pallas kernel's
    mixed-precision mode — then cast back to the input dtype.
    """
    precision = check_precision(precision)
    xc = x - jnp.mean(x, axis=0, keepdims=True)
    if precision == "fp32":
        return jnp.matmul(xc, xc.T, precision=dot_precision(xc.dtype))
    xb = xc.astype(jnp.bfloat16)
    g = jnp.matmul(xb, xb.T, preferred_element_type=jnp.float32)
    return g.astype(x.dtype)
