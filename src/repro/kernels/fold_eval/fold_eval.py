"""Pallas TPU kernel: fused fold evaluation  ė_Te = (I − H_Te)⁻¹ (y_Te − H·y).

The Eq. 14 hot path used to be two kernel launches with an (N, B) HBM
round-trip between them: ``hat_apply`` writes the full-fit errors
Ê = Y − HY, then ``foldsolve`` gathers Ê_Te and runs the per-fold masked
Gauss-Jordan solves. This kernel fuses them in the FlashAttention style
(blocked contraction + in-VMEM epilogue, Dao et al. 2022): each fold's
grid pass streams the fold's *hat-row tiles* H[te_k, :] over the N
contraction chunks, accumulates the fold's ê block in a VMEM scratch
accumulator, and — on the last chunk — runs the fold solve in place on
that block, so the intermediate (N, B) Ê is never materialised. Only the
(K, m, B) solves ė_Te (and the matching ê_Te block, which the wrapper's
residual-checked jitter fallback needs) reach HBM.

Grid: (K, B/bb, N/bn) with the contraction axis innermost (the TPU
output-revisiting pattern — the accumulator block (k, j) stays resident
in VMEM across consecutive steps). The solve epilogue reuses the same
masked Gauss-Jordan core as the standalone ``foldsolve`` kernel
(:func:`repro.kernels.foldsolve.foldsolve.gauss_jordan_solve`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import dot_precision
from repro.kernels.foldsolve.foldsolve import gauss_jordan_solve

DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_B = 128


def _fold_eval_kernel(h_rows_ref, h_te_ref, y_ref, y_te_ref,
                      t_ref, e_ref, acc_ref, *, m: int, n_chunks: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the hat_apply contraction, restricted to this fold's te rows:
    # acc += H[te_k, chunk] @ Y[chunk]   →   (H·y)_Te after the last chunk
    acc_ref[...] += jnp.dot(h_rows_ref[0], y_ref[...],
                            precision=dot_precision(y_ref.dtype),
                            preferred_element_type=acc_ref.dtype)

    @pl.when(c == n_chunks - 1)
    def _solve():
        e = y_te_ref[0].astype(acc_ref.dtype) - acc_ref[...]   # ê_Te block
        e_ref[0] = e.astype(e_ref.dtype)
        a = jnp.eye(m, dtype=acc_ref.dtype) - h_te_ref[0].astype(acc_ref.dtype)
        t_ref[0] = gauss_jordan_solve(a, e).astype(t_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_b", "interpret"))
def fold_eval_pallas(h_rows: jax.Array, h_te: jax.Array, y: jax.Array,
                     y_te: jax.Array, *, block_n: int = DEFAULT_BLOCK_N,
                     block_b: int = DEFAULT_BLOCK_B, interpret: bool = False):
    """Fused ė_Te = (I − H_Te)⁻¹ (y_Te − H·y) per fold; returns (ė_Te, ê_Te).

    h_rows: (K, m, N) hat rows H[te_k, :] per fold.
    h_te:   (K, m, m) diagonal fold blocks H_Te (jitter, if any, is folded
            in by the wrapper as h_te − εI, so the kernel stays shift-free).
    y:      (N, B) label batch.   y_te: (K, m, B) gathered test labels.
    N % block_n == 0 and B % block_b == 0 (the ops wrapper pads).
    """
    k, m, n = h_rows.shape
    b = y.shape[1]
    assert n % block_n == 0 and b % block_b == 0, (n, b, block_n, block_b)
    grid = (k, b // block_b, n // block_n)
    acc_dtype = jnp.float32 if y.dtype in (jnp.bfloat16, jnp.float16, jnp.float32) else y.dtype

    return pl.pallas_call(
        functools.partial(_fold_eval_kernel, m=m, n_chunks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, m, block_n), lambda i, j, c: (i, 0, c)),
            pl.BlockSpec((1, m, m), lambda i, j, c: (i, 0, 0)),
            pl.BlockSpec((block_n, block_b), lambda i, j, c: (c, j)),
            pl.BlockSpec((1, m, block_b), lambda i, j, c: (i, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, m, block_b), lambda i, j, c: (i, 0, j)),
            pl.BlockSpec((1, m, block_b), lambda i, j, c: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, m, b), y.dtype),
            jax.ShapeDtypeStruct((k, m, b), y.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((m, block_b), acc_dtype)],
        interpret=interpret,
    )(h_rows, h_te, y, y_te)
