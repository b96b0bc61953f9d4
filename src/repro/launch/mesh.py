"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import to obtain placeholder devices; everything else sees the real
device count.

Mesh axes:
  single-pod:  ("data", "model")         = (16, 16)  -> 256 chips (v5e pod)
  multi-pod:   ("pod", "data", "model")  = (2, 16, 16) -> 512 chips

"model" carries TP/SP/EP; ("pod", "data") carry DP; "data" additionally
carries ZeRO-1 optimizer-state sharding; the paper's permutation/searchlight
workloads shard their embarrassingly-parallel problem axis over "pod".

The axes are ``Auto``: the LM substrate places its arrays with sharding
constraints (``repro.launch.sharding``) and leaves the rest to the
compiler. On ``Explicit`` axes (the ``jax.make_mesh`` default) every op
must resolve its own output sharding, and its embedding gathers cannot.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    data = n // model_axis
    return _auto_mesh((data, model_axis), ("data", "model"))


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
