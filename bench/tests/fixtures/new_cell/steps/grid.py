"""``grid``: binary cross-validation at every point of the subject's (Q, N, P) grid.

One ``Workload(kind="grid")`` per visit, with the subject's folds and λ.
The check refits every point in float64 and counts the test trials by
which each point's accuracy departs from the program's.
"""

import numpy as np

from bench.reference import refit


def run(client, engine, subj, step, seed) -> dict:
    from repro.serve import DatasetSpec, Workload

    spec = DatasetSpec(None, (subj.te, subj.tr), subj.lam)
    resp = client.submit(Workload(kind="grid", xs=subj.x, y=subj.y, dataset=spec))
    grid = np.asarray(resp.accuracies)
    return {"grid": grid, "labels": grid.size}


def _accuracies(subj, prec) -> np.ndarray:
    y = subj.y[:, None]
    return np.array([refit.fold_accuracy(
        refit.binary_dvals(x, y, subj.te, subj.tr, subj.lam, prec), y[subj.te])[0]
        for x in subj.x64])


def reference(subj, prod, rng, traffic, control) -> dict:
    """Test trials of disagreement at every grid point, per point."""
    got = prod["grid"] if control is None else _accuracies(subj, control)
    gap = np.abs(np.asarray(got, np.float64) - _accuracies(subj, refit.FLOAT64)) * subj.te.size
    return {"flips_per_1k": (float(np.rint(gap).sum()), gap.size)}
