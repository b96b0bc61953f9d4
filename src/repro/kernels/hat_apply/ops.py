"""Jit'd public wrapper for hat_apply (padding + dispatch)."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.common import check_device_dtypes, default_interpret, pad_to
from repro.kernels.hat_apply.hat_apply import hat_apply_pallas

__all__ = ["hat_errors"]


@functools.partial(jax.jit, static_argnames=("block_n", "block_b", "interpret"))
def hat_errors(h: jax.Array, y: jax.Array, *, block_n: Optional[int] = None,
               block_b: Optional[int] = None, interpret: Optional[bool] = None):
    """ê = y − H y for a label batch y (N,) or (N, B) — Algorithm 1 inner step.

    Zero-padding N is safe: padded rows/cols of H are zero so padded entries
    of E are y_pad − 0 = 0 and are sliced away.
    """
    if interpret is None:
        interpret = default_interpret()
    check_device_dtypes("hat_apply", interpret, h, y)
    squeeze = y.ndim == 1
    yb = y[:, None] if squeeze else y
    n, b = yb.shape
    bn = min(block_n or 256, max(8, 1 << (n - 1).bit_length()))
    bb = min(block_b or 128, max(8, 1 << (b - 1).bit_length()))
    hp = pad_to(pad_to(h, bn, 0), bn, 1)
    yp = pad_to(pad_to(yb, bn, 0), bb, 1)
    e = hat_apply_pallas(hp, yp, block_n=bn, block_b=bb, interpret=interpret)
    e = e[:n, :b]
    return e[:, 0] if squeeze else e
