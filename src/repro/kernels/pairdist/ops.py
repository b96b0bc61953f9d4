"""Jit'd public wrapper for the pairdist kernel: padding, norms, dispatch."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import check_device_dtypes, default_interpret, pad_to
from repro.kernels.pairdist.pairdist import NORM_LANES, pairdist_pallas

__all__ = ["pairwise_sq_dists"]


@functools.partial(jax.jit, static_argnames=("block_c", "block_p", "interpret"))
def pairwise_sq_dists(u: jax.Array, *, block_c: Optional[int] = None,
                      block_p: Optional[int] = None,
                      interpret: Optional[bool] = None) -> jax.Array:
    """D[i, j] = ‖u_i − u_j‖² for U (C, P) via the Pallas kernel.

    Inputs of arbitrary (C, P) are zero-padded to block multiples (padded
    rows have zero norms and contribute nothing inside the real block) and
    sliced away on return. Blocks shrink to the (padded) matrix size for
    small problems — condition counts are typically tiny.
    """
    if interpret is None:
        interpret = default_interpret()
    check_device_dtypes("pairdist", interpret, u)
    c, p = u.shape
    bc = min(block_c or 128, max(8, 1 << (c - 1).bit_length()))
    bp = min(block_p or 512, max(8, 1 << (p - 1).bit_length()))
    up = pad_to(pad_to(u, bc, 0), bp, 1)
    norms = jnp.sum(up * up, axis=1)
    norms = jnp.broadcast_to(norms[:, None], (up.shape[0], NORM_LANES))
    d = pairdist_pallas(up, norms, block_c=bc, block_p=bp,
                        interpret=interpret)
    return d[:c, :c]
