"""Percentiles, as the benchmark reports them (copied from ``benchmarks/common.py``)."""

from __future__ import annotations

import numpy as np

__all__ = ["percentile"]


def percentile(samples, q: float) -> float:
    """The q-th percentile (0-100) of a sample, NumPy's linear interpolation."""
    return float(np.percentile(np.asarray(samples, dtype=float), q))

