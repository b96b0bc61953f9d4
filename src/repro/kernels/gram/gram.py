"""Pallas TPU kernel: tiled Gram matrix G = X Xᵀ (syrk).

This is the dominant O(N²P) term of the analytical CV setup in the paper's
P ≫ N regime (DESIGN.md §2): the dual hat matrix needs G_c = X_c X_cᵀ once,
after which every fold/permutation is O(m²). On TPU the contraction runs on
the MXU with (bn × bp)·(bp × bn) tiles resident in VMEM, accumulating over
the feature-chunk grid axis in an f32 VMEM scratch accumulator.

Grid: (N/bn, N/bn, P/bp) — the contraction axis is the *last* (innermost)
grid dimension so the output block (i, j) is revisited on consecutive steps
(the TPU output-revisiting pattern; the accumulator stays in VMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import dot_precision

DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_P = 512


def _gram_kernel(x_i_ref, x_j_ref, out_ref, acc_ref, *, n_chunks: int):
    """One (i, j, kp) grid step: acc += X_i[kp] @ X_j[kp]ᵀ."""
    kp = pl.program_id(2)

    @pl.when(kp == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_i_ref[...], x_j_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=dot_precision(x_i_ref.dtype),
        preferred_element_type=acc_ref.dtype,
    )

    @pl.when(kp == n_chunks - 1)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_p", "interpret"))
def gram_pallas(x: jax.Array, *, block_n: int = DEFAULT_BLOCK_N,
                block_p: int = DEFAULT_BLOCK_P, interpret: bool = False) -> jax.Array:
    """G = X @ Xᵀ for X of shape (N, P); N % block_n == 0, P % block_p == 0.

    (The public wrapper in ops.py handles padding/centering.)
    """
    n, p = x.shape
    assert n % block_n == 0 and p % block_p == 0, (n, p, block_n, block_p)
    grid = (n // block_n, n // block_n, p // block_p)
    if x.dtype in (jnp.bfloat16, jnp.float16):
        acc_dtype, out_dtype = jnp.float32, jnp.float32
    else:
        acc_dtype, out_dtype = x.dtype, x.dtype

    return pl.pallas_call(
        functools.partial(_gram_kernel, n_chunks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_p), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_n, block_p), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((block_n, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_n, block_n), acc_dtype)],
        interpret=interpret,
    )(x, x)
