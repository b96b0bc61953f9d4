"""Decide ``correct``: what the window's timed path produced against the float64 refit.

After the window has closed and the program's state is freed, a sample
drawn from the run's seed is recomputed by :mod:`bench.reference.refit`
(NumPy float64, a refit per fold) and compared number by number; each
number has its limit in ``bench/limits/<cell>.json``.

``flips_per_1k``
    Closed loops. For each checked visit, the label vectors it produced
    answers for — the ``cv`` labels (every prediction compared), the
    observed labels and a sample of the permutation draws (accuracy
    compared) — and the test trials on which program and reference
    disagree, counted per 1000 label vectors. The draws are regenerated
    from the visit's seed by :mod:`bench.reference.draws`.
``dv_rel_dev``
    The open HTTP loop. For each sampled request, the largest gap between
    the served binary-LDA decision values and the reference's, over the
    reference's largest magnitude; the worst request counts.
``failed``
    Requests that never came back or came back as errors, from the
    window's own count; limit 0. A sampled request that failed is left
    out of ``dv_rel_dev`` and counted here.
"""

from __future__ import annotations

import numpy as np

from bench.reference import refit
from bench.reference.draws import permutation_indices

__all__ = ["check_closed", "check_open", "verdict"]


def _sample(rng, count: int, k: int) -> np.ndarray:
    return np.sort(rng.choice(count, min(k, count), replace=False))


def check_closed(cell, subjects, products, seed: int, control=None):
    """Numbers of a closed-loop run from the window's products.

    With ``control`` (a :class:`bench.reference.refit.Precision`), the
    reference computed at that precision answers the same label vectors in
    the program's place.
    """
    traffic = cell.traffic
    rng = np.random.default_rng([int(seed), 11])
    visits = _sample(rng, len(products), int(traffic["check_visits"]))
    trials = vectors = 0
    for v in visits:
        prod = products[v]
        subj = subjects[prod["subject"]]
        y, te = subj.y, subj.te
        cols = [y]
        got = []
        if "null" in prod:
            draws = _sample(rng, prod["n_perm"], int(traffic["check_draws"]))
            perms = permutation_indices(prod["seed"], len(y), prod["n_perm"])[draws]
            cols += list(y[perms])
            got = [prod["observed"]] + list(np.asarray(prod["null"])[draws])
        labels = np.stack(cols)  # (B, N)
        acc, preds = _answers(subj, labels, refit.FLOAT64)
        cv = prod.get("cv")
        if control is not None:
            acc_c, preds_c = _answers(subj, labels, control)
            got = list(acc_c[:len(got)])
            cv = None if cv is None else preds_c[0]
        if cv is not None:
            trials += int((np.asarray(cv) != preds[0]).sum())
            vectors += 1
        if got:
            gap = np.abs(np.asarray(got, np.float64) - acc[:len(got)]) * te.size
            trials += float(np.rint(gap).sum())
            vectors += len(got)
    return {"flips_per_1k": 1000.0 * trials / max(vectors, 1)}


def _answers(subj, labels, prec):
    """(accuracy per label row (B,), predictions (B, K, m) or None) of the refit."""
    x, te, tr, lam = subj.x64, subj.te, subj.tr, subj.lam
    if subj.num_classes == 2:
        dv = refit.binary_dvals(x, labels.T, te, tr, lam, prec)
        return refit.fold_accuracy(dv, labels.T[te]), None
    preds, _ = refit.multiclass_predict(x, labels, te, tr, lam, subj.num_classes, prec)
    return (preds == labels[:, te]).mean(axis=(1, 2)), preds


def check_open(subject, sampled, control=None):
    """Numbers of an open-loop run from its sampled (index, labels, values).

    With ``control``, the reference at that precision answers the same
    requests in the program's place.
    """
    worst = 0.0
    live = [(y, v) for _, y, v in sampled if v is not None]
    if live:
        ys = np.stack([y for y, _ in live], axis=1)
        args = (subject.x64, ys, subject.te, subject.tr, subject.lam)
        ref = refit.binary_dvals(*args)
        if control is not None:
            got_all = refit.binary_dvals(*args, control)
            live = [(y, got_all[..., j]) for j, (y, _) in enumerate(live)]
        for j, (_, got) in enumerate(live):
            r = ref[..., j]
            worst = max(worst, float(np.abs(np.asarray(got) - r).max() / np.abs(r).max()))
    return {"dv_rel_dev": worst}


def verdict(numbers: dict, limits: dict) -> tuple[bool, list]:
    """(correct, [(name, value, limit)]) — every limited number at or under its limit."""
    rows = []
    ok = True
    for name, spec in limits.items():
        if name not in numbers:
            raise KeyError(f"the limits name {name!r}, which this run does not compare")
        value, limit = float(numbers[name]), float(spec["limit"])
        rows.append((name, value, limit))
        ok = ok and value <= limit
    return ok, rows
