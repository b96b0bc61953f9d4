"""Pallas TPU kernel: tiled pairwise squared Euclidean distances.

D[i, j] = ‖u_i − u_j‖² = ‖u_i‖² + ‖u_j‖² − 2·u_i·u_j — the RSA pattern-RDM
hot-spot (condition-mean RDMs, model RDMs from feature embeddings). The
cross-product term is the same MXU-friendly (bc × bp)·(bp × bc) contraction
as the ``gram`` kernel, accumulated over the feature-chunk grid axis in an
f32 VMEM scratch; the precomputed squared row norms ride along as a
lane-replicated (C, 128) input so the distance assembly happens in-kernel
on the final feature chunk (one fused pass, no (C, C) intermediate in HBM).

Grid: (C/bc, C/bc, P/bp) — contraction axis innermost so the output block
(i, j) is revisited on consecutive steps (the TPU output-revisiting
pattern; the accumulator stays in VMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import dot_precision

DEFAULT_BLOCK_C = 128
DEFAULT_BLOCK_P = 512

NORM_LANES = 128  # squared norms are lane-replicated to the TPU tile width


def _pairdist_kernel(u_i_ref, u_j_ref, n_i_ref, n_j_ref, out_ref, acc_ref,
                     *, n_chunks: int):
    """One (i, j, kp) grid step: acc += U_i[kp] @ U_j[kp]ᵀ; assemble at end."""
    kp = pl.program_id(2)

    @pl.when(kp == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        u_i_ref[...], u_j_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=dot_precision(u_i_ref.dtype),
        preferred_element_type=acc_ref.dtype,
    )

    @pl.when(kp == n_chunks - 1)
    def _store():
        n_i = n_i_ref[:, 0].astype(acc_ref.dtype)              # (bc,)
        n_j = n_j_ref[:, 0].astype(acc_ref.dtype)
        d = n_i[:, None] + n_j[None, :] - 2.0 * acc_ref[...]
        out_ref[...] = jnp.maximum(d, 0.0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "block_p", "interpret"))
def pairdist_pallas(u: jax.Array, norms: jax.Array, *,
                    block_c: int = DEFAULT_BLOCK_C,
                    block_p: int = DEFAULT_BLOCK_P,
                    interpret: bool = False) -> jax.Array:
    """D = pairwise sq. distances for U (C, P); norms (C, NORM_LANES) holds
    ‖u_i‖² lane-replicated. C % block_c == 0, P % block_p == 0.

    (The public wrapper in ops.py handles padding and norm preparation.)
    """
    c, p = u.shape
    assert c % block_c == 0 and p % block_p == 0, (c, p, block_c, block_p)
    assert norms.shape == (c, NORM_LANES), norms.shape
    grid = (c // block_c, c // block_c, p // block_p)
    if u.dtype in (jnp.bfloat16, jnp.float16):
        acc_dtype, out_dtype = jnp.float32, jnp.float32
    else:
        acc_dtype, out_dtype = u.dtype, u.dtype

    return pl.pallas_call(
        functools.partial(_pairdist_kernel, n_chunks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_c, block_p), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_c, block_p), lambda i, j, k: (j, k)),
            pl.BlockSpec((block_c, NORM_LANES), lambda i, j, k: (i, 0)),
            pl.BlockSpec((block_c, NORM_LANES), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_c, block_c), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((c, c), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_c), acc_dtype)],
        interpret=interpret,
    )(u, u, norms, norms)
