"""Label vectors and arrival schedules made from a seed with NumPy alone.

Shared by the run's process and the HTTP load generator, which imports
no JAX, so both sides make the same labels for request i.
"""

from __future__ import annotations

import numpy as np

__all__ = ["arrival_offsets", "random_split", "derived_seeds"]


def derived_seeds(seed: int, stream: int, count: int) -> np.ndarray:
    """``count`` integers in [0, 2**31 − 1) for one stream of a run's seed."""
    rng = np.random.default_rng([int(seed), int(stream)])
    return rng.integers(0, 2**31 - 1, size=count, dtype=np.int64)


def random_split(seed: int, index: int, n: int) -> np.ndarray:
    """Request ``index``'s ±1 labels (float32, (n,)): a fresh balanced split of n trials."""
    rng = np.random.default_rng([int(seed), 7, int(index)])
    y = np.repeat(np.array([-1.0, 1.0], np.float32), [n // 2, n - n // 2])
    return rng.permutation(y)


def arrival_offsets(seed: int, rate_per_s: float, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of an open loop at ``rate_per_s``.

    The gaps are the exact quantiles of the exponential distribution of a
    Poisson process at that rate, so every seed offers the same gaps and the
    same total load; the seed only shuffles their order.
    """
    count = max(1, int(round(rate_per_s * seconds)))
    u = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-u) / rate_per_s
    rng = np.random.default_rng([int(seed), 5])
    return np.cumsum(rng.permutation(gaps))
