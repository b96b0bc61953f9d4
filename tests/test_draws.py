"""Permutation draws carry the labels: ``permutation_draws(key, y, T)`` is
``y`` taken at the index draws ``permutation_indices(key, len(y), T)``,
exactly, for every payload a null shuffles.

The shuffle sorts the payload by random keys with a stable sort, so its
moves depend on the keys alone. The served nulls read these rows, and the
benchmark's reference rebuilds the same draws as index rows, so equality
here is what keeps the two in step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.permutation import permutation_draws, permutation_indices

KEYS = [0, 1, 7, 42, 2**31 + 11, 123456789, 3, 99, 2024, 65537]


def _payload(kind: str, n: int):
    rng = np.random.default_rng(n)
    if kind == "pm1_f32":  # binary labels as the engine casts them on the chip
        return jnp.asarray(np.where(rng.permutation(n) % 2 == 0, -1.0, 1.0), jnp.float32)
    if kind == "int3":  # 3-class labels
        return jnp.asarray(rng.permutation(n) % 3, jnp.int32)
    return jnp.arange(n)  # the index row itself


CASES = [(kind, n, t) for kind in ("pm1_f32", "int3", "arange")
         for n in (787, 13) for t in (20, 32, 1000, 1024)]


@pytest.mark.parametrize("kind,n,n_perm", CASES,
                         ids=[f"{k}-n{n}-t{t}" for k, n, t in CASES])
def test_label_draws_equal_labels_at_index_draws(kind, n, n_perm):
    payload = _payload(kind, n)
    y = np.asarray(payload)
    for seed in KEYS:
        key = jax.random.PRNGKey(seed)
        draws = permutation_draws(key, payload, n_perm)
        idx = np.asarray(permutation_indices(key, n, n_perm))
        assert draws.shape == (n_perm, n) and draws.dtype == payload.dtype
        np.testing.assert_array_equal(np.asarray(draws), y[idx])
        # prefix-stable: the leading draws do not depend on T
        np.testing.assert_array_equal(np.asarray(draws[:20]),
                                      np.asarray(permutation_draws(key, payload, 20)))
        if kind == "arange":
            np.testing.assert_array_equal(np.asarray(draws), idx)
