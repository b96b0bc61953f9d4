"""``permutation``: one permutation test of ``n_perm`` draws, with the visit's seed.

The products are the null (``null``), the observed accuracy
(``observed``) and ``n_perm``. The check recomputes the observed labels
and ``check_draws`` draws sampled from the check's generator, regenerated
from the visit's seed by :mod:`bench.reference.draws`, and counts the test
trials by which each accuracy departs from the float64 refit's.
"""

import numpy as np

from bench import check
from bench.reference import refit
from bench.reference.draws import permutation_indices


def run(client, engine, subj, step, seed) -> dict:
    from repro.serve import Workload

    n_perm = int(step["n_perm"])
    resp = client.submit(Workload(kind="permutation", n_perm=n_perm, seed=int(seed),
                                  **subj.workload_args()))
    null = np.asarray(resp.null)
    return {"null": null, "observed": float(resp.observed), "n_perm": n_perm,
            "labels": null.size + 1}


def reference(subj, prod, rng, traffic, control) -> dict:
    """Test trials of disagreement over the observed and the sampled draws' labels.

    With ``control``, the reference at that precision answers the same
    label vectors in the program's place.
    """
    y = subj.y
    draws = check.sample(rng, prod["n_perm"], int(traffic["check_draws"]))
    perms = permutation_indices(prod["seed"], len(y), prod["n_perm"])[draws]
    labels = np.stack([y] + list(y[perms]))  # (B, N)
    if control is None:
        got = np.asarray([prod["observed"]] + list(np.asarray(prod["null"])[draws]), np.float64)
    else:
        got = check.accuracy(subj, labels, control)
    gap = np.abs(got - check.accuracy(subj, labels, refit.FLOAT64)) * subj.te.size
    return {"flips_per_1k": (float(np.rint(gap).sum()), len(labels))}
