"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling, MXU-aligned
block shapes). On this CPU-only container they are validated with
``interpret=True`` which executes the kernel bodies in Python; the
``interpret`` default below auto-detects the platform so the same call
sites run compiled on real TPUs.

The chip path is float32: the TPU compiler has no float64, so the wrappers
refuse a float64 operand on a compiled (non-interpret) call with a
:class:`TypeError` instead of Mosaic's bare ``NotImplementedError``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "default_interpret",
    "default_fused",
    "check_device_dtypes",
    "dot_precision",
    "pad_to",
    "cdiv",
]


def default_interpret() -> bool:
    """interpret=True off-TPU (CPU validation), False on real TPUs."""
    return jax.default_backend() != "tpu"


def default_fused() -> bool:
    """Resolve ``fused=None`` (auto): use the fused fold_eval kernel only
    where Pallas compiles natively. Off-TPU the kernels run in interpret
    mode (Python-speed), so auto keeps the reference XLA path — the fused
    path stays reachable everywhere by passing ``fused=True`` explicitly.
    """
    return jax.default_backend() == "tpu"


def check_device_dtypes(kernel: str, interpret: bool, *arrays: jax.Array) -> None:
    """Raise TypeError if a compiled kernel call would receive float64."""
    if interpret:
        return
    for a in arrays:
        if a.dtype == jnp.float64:
            raise TypeError(
                f"{kernel}: float64 operand of shape {a.shape} on the compiled TPU "
                "path; the TPU has no float64. Build plans and labels in float32 "
                "(leave jax_enable_x64 off, or cast with .astype(jnp.float32))."
            )


def dot_precision(dtype):
    """Contraction precision for operands of ``dtype``.

    float32 operands get ``HIGHEST``: the TPU's default for a float32
    matmul is a single bfloat16 pass, which is not the float32 the
    ``precision="fp32"`` build promises. Narrower operands (the
    ``bf16_gram`` mode) keep the default single pass. A no-op on CPU.
    Every served contraction takes its precision from here;
    ``tests/test_precision.py`` walks the served families' jaxprs to keep
    it that way.
    """
    if jnp.dtype(dtype).itemsize >= 4:
        return jax.lax.Precision.HIGHEST
    return None


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pad_to(x: jax.Array, multiple: int, axis: int) -> jax.Array:
    """Zero-pad ``axis`` up to the next multiple (MXU alignment)."""
    size = x.shape[axis]
    target = cdiv(size, multiple) * multiple
    if target == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad)
