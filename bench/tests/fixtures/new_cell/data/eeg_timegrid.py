"""Time-resolved decoding data: the channel vector at every time point, a (Q, N, P) grid.

The ``eeg`` generator's baseline-corrected epochs (:func:`bench.data.eeg.epochs`),
kept at every sample instead of averaged over windows: Q = the epoch's
time points, N = trials, P = channels. One jitted call per subject.
"""

from functools import partial

import jax
import jax.numpy as jnp

from bench.data import eeg


@partial(jax.jit, static_argnames=("n_trials", "n_channels", "num_classes", "fs", "t_min",
                                   "t_max", "snr"))
def _simulate(key, **shape):
    ep, y = eeg.epochs(key, **shape)  # (N, ch, T)
    return jnp.transpose(ep, (2, 0, 1)), y


def simulate(seed: int, data: dict, num_classes: int):
    """One subject: (x float32 (Q, N, P), y int32 (N,)), both on the device."""
    return _simulate(jax.random.PRNGKey(seed), n_trials=int(data["n_trials"]),
                     n_channels=int(data["n_channels"]), num_classes=int(num_classes),
                     fs=float(data["fs"]), t_min=float(data["t_min"]),
                     t_max=float(data["t_max"]), snr=float(data["snr"]))
