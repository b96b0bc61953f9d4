"""Simulated EEG/MEG subject at the source paper's §2.13 shape, made on the device.

A copy of the repository's EEG simulator, kept with the benchmark so that
no change to the program can move the data it is measured on. One jitted
call turns a key into one subject's windowed features:

* epochs of ``n_channels`` channels sampled at ``fs`` Hz from ``t_min`` to
  ``t_max`` seconds, a class-specific N170-like evoked component
  (``snr``-scaled) in spatially correlated noise, baseline-corrected on the
  pre-stimulus interval;
* features: channel amplitudes averaged over consecutive post-stimulus
  windows of ``window_ms`` and concatenated (100 ms → P = 3800, 200 ms →
  P = 1900 at 380 channels).

Labels cycle through the classes (trial i has class i mod C), as in the
original simulator. The epochs (n_trials × n_channels × n_times float32)
live only inside the call.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["n_features", "simulate"]


def _post_windows(n_times: int, t_min: float, fs: float, window_ms: float) -> tuple[int, int, int]:
    """(first post-stimulus sample, samples per window, number of windows)."""
    times = t_min + np.arange(n_times) / fs
    first = int(np.flatnonzero(times > 1e-9)[0])
    per_win = int(round(window_ms / 1000.0 * fs))
    return first, per_win, (n_times - first) // per_win


def n_features(shape: dict) -> int:
    """P for a configuration's ``data`` block."""
    n_times = int(round((shape["t_max"] - shape["t_min"]) * shape["fs"])) + 1
    _, _, n_win = _post_windows(n_times, shape["t_min"], shape["fs"], shape["window_ms"])
    return n_win * shape["n_channels"]


@partial(jax.jit, static_argnames=("n_trials", "n_channels", "num_classes", "fs", "t_min",
                                   "t_max", "window_ms", "snr"))
def _simulate(key, *, n_trials, n_channels, num_classes, fs, t_min, t_max, window_ms, snr):
    dtype = jnp.float32
    n_times = int(round((t_max - t_min) * fs)) + 1
    times = t_min + jnp.arange(n_times, dtype=dtype) / fs
    k_pat, k_noise, k_mix = jax.random.split(key, 3)
    patterns = jax.random.normal(k_pat, (num_classes, n_channels), dtype)
    patterns = patterns / jnp.linalg.norm(patterns, axis=1, keepdims=True)
    latencies = 0.17 + 0.03 * jnp.arange(num_classes, dtype=dtype)
    erp = jnp.exp(-0.5 * ((times[None, :] - latencies[:, None]) / 0.05) ** 2)
    erp = erp * (times[None, :] > 0)
    signal = patterns[:, :, None] * erp[:, None, :]  # (C, ch, t)
    y = jnp.arange(n_trials, dtype=jnp.int32) % num_classes
    mix = jax.random.normal(k_mix, (n_channels, n_channels), dtype) / jnp.sqrt(
        jnp.asarray(n_channels, dtype))
    white = jax.random.normal(k_noise, (n_trials, n_channels, n_times), dtype)
    noise = jnp.einsum("cd,ndt->nct", mix, white, precision=jax.lax.Precision.HIGHEST)
    epochs = snr * signal[y] + noise
    pre = (times < 0).astype(dtype)
    base = jnp.einsum("nct,t->nc", epochs, pre, precision=jax.lax.Precision.HIGHEST) / pre.sum()
    epochs = epochs - base[:, :, None]
    first, per_win, n_win = _post_windows(n_times, t_min, fs, window_ms)
    post = epochs[:, :, first:first + n_win * per_win]
    feats = post.reshape(n_trials, n_channels, n_win, per_win).mean(-1)  # (N, ch, win)
    x = jnp.transpose(feats, (0, 2, 1)).reshape(n_trials, n_win * n_channels)
    return x, y


def simulate(seed: int, shape: dict, num_classes: int):
    """One subject: (x float32 (N, P) on the device, y int32 (N,) on the device).

    ``shape`` is a configuration's ``data`` block; ``seed`` any integer in
    [0, 2**31).
    """
    return _simulate(
        jax.random.PRNGKey(seed),
        n_trials=int(shape["n_trials"]),
        n_channels=int(shape["n_channels"]),
        num_classes=int(num_classes),
        fs=float(shape["fs"]),
        t_min=float(shape["t_min"]),
        t_max=float(shape["t_max"]),
        window_ms=float(shape["window_ms"]),
        snr=float(shape["snr"]),
    )
