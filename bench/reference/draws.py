"""Permutation draws as the served API promises them, from the workload's seed.

Draw t of a workload with seed s is ``jax.random.permutation`` under the key
``fold_in(PRNGKey(s), t)``: prefix-stable, so any number of leading draws
can be regenerated without the rest. Written here from that contract, not
taken from the program, so the reference reads the draws independently.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["permutation_indices"]


@partial(jax.jit, static_argnames=("n", "n_perm"))
def _draws(key, n, n_perm):
    keys = jax.vmap(lambda t: jax.random.fold_in(key, t))(jnp.arange(n_perm))
    return jax.vmap(lambda k: jax.random.permutation(k, n))(keys)


def permutation_indices(seed: int, n: int, n_perm: int) -> np.ndarray:
    """(n_perm, n) int index rows of the first ``n_perm`` draws for ``seed``."""
    return np.asarray(_draws(jax.random.PRNGKey(seed), n, n_perm))
