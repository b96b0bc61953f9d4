"""Pallas TPU kernel: fused permutation-batch hat application.

Computes, in one pass over H,

    Yhat = H @ Y        and        E = Y - H @ Y

for a permutation batch Y of shape (N, B) (Algorithm 1's inner product
``ŷ ← H yσ`` for B permutations at once). Fusing the subtraction saves one
full (N, B) HBM round-trip per permutation chunk — on TPU this matmul is
HBM-bandwidth-bound for the small B of a chunk, so the fusion removes a
third of the memory traffic (write ŷ, read ŷ, write ê → write ê only).

Grid: (N/bn, B/bb, N/bk), contraction over the last axis with an f32 VMEM
accumulator; the Y_Te diagonal block needed for the subtraction is the
second input with a (i, b)-indexed BlockSpec.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import dot_precision

DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_B = 128


def _hat_apply_kernel(h_ref, y_k_ref, y_i_ref, err_ref, acc_ref, *, n_chunks: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(h_ref[...], y_k_ref[...],
                            precision=dot_precision(h_ref.dtype),
                            preferred_element_type=acc_ref.dtype)

    @pl.when(k == n_chunks - 1)
    def _store():
        err_ref[...] = (y_i_ref[...].astype(acc_ref.dtype)
                        - acc_ref[...]).astype(err_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_b", "interpret"))
def hat_apply_pallas(h: jax.Array, y: jax.Array, *, block_n: int = DEFAULT_BLOCK_N,
                     block_b: int = DEFAULT_BLOCK_B, interpret: bool = False):
    """E = Y − H Y. h: (N, N), y: (N, B); N % block_n == 0, B % block_b == 0."""
    n, b = y.shape
    assert h.shape == (n, n)
    assert n % block_n == 0 and b % block_b == 0
    grid = (n // block_n, b // block_b, n // block_n)
    acc_dtype = jnp.float32 if h.dtype in (jnp.bfloat16, jnp.float16, jnp.float32) else h.dtype

    return pl.pallas_call(
        functools.partial(_hat_apply_kernel, n_chunks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_n), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_n, block_b), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_n, block_b), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_b), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, b), h.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, block_b), acc_dtype)],
        interpret=interpret,
    )(h, y, y)
