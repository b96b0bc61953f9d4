"""Operations and bytes the algorithm needs, from the shapes alone.

These count the work as the paper's algorithm defines it, whatever code
performs it, so a roofline share means the same before and after a change
to the program. Bytes are the least traffic: every input read once and
every output written once, in float32 (int32 indices); they ignore caches
and re-reads, and say so by being a lower bound.
"""

from __future__ import annotations

__all__ = ["binary_eval", "gram", "least_time"]

F32 = 4


def binary_eval(n: int, k: int, m: int, b: int) -> tuple[float, float]:
    """(flops, bytes) of binary-LDA CV decision values for b label vectors.

    ŷ = H y (2·N²·b); per fold the two triangular solves against the
    Cholesky factor of I − H_Te (m² each per column, K folds); the
    train-block product H_{Tr,Te}·ė_Te of the bias adjustment
    (2·(N−m)·m per column, K folds). Reads H, the K factors, the K train
    blocks and b permutation index rows; writes b results.
    """
    flops = 2.0 * n * n * b + 2.0 * k * m * m * b + 2.0 * k * (n - m) * m * b
    nbytes = F32 * (n * n + k * m * m + k * (n - m) * m + n * b + b)
    return flops, nbytes


def gram(n: int, p: int) -> tuple[float, float]:
    """(flops, bytes) of the centred Gram X_c X_cᵀ: 2·N²·P; reads X, writes G."""
    return 2.0 * n * n * p, F32 * (n * p + n * n)


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time (s) and which bound sets it ("compute" | "memory")."""
    t_compute = flops / peak["flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
