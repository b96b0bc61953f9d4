"""Decide ``correct``: what the window's timed path produced against the float64 refit.

After the window has closed and the program's state is freed, a sample
drawn from the run's seed is recomputed by :mod:`bench.reference.refit`
(NumPy float64, a refit per fold) and compared number by number; each
number has its limit in ``bench/limits/<cell>.json``.

``flips_per_1k``
    Closed loops. Each step of a checked visit that produces answers
    compares them in its own ``reference`` (``bench/steps/<step>.py``) and
    gives the test trials on which program and reference disagree, and the
    label vectors it compared: the ``cv`` labels (every prediction
    compared), the observed labels and a sample of the permutation draws
    (accuracy compared). The trials are counted per 1000 label vectors.
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

__all__ = ["accuracy", "check_closed", "check_open", "sample", "verdict"]


def sample(rng, count: int, k: int) -> np.ndarray:
    """``min(k, count)`` sorted indices of ``range(count)``, without repeats."""
    return np.sort(rng.choice(count, min(k, count), replace=False))


def check_closed(cell, subjects, products, seed: int, control=None):
    """Numbers of a closed-loop run from the window's products.

    The visits to check are drawn from the run's seed; each one's steps,
    in the mix's order, compare their answers (``reference`` of each step
    module, drawing from the same generator), and each number sums its
    steps' ``(count, per)`` into ``1000 · count / per``. With ``control`` (a
    :class:`bench.reference.refit.Precision`), the reference computed at
    that precision answers the same label vectors in the program's place.
    """
    traffic = cell.traffic
    rng = np.random.default_rng([int(seed), 11])
    totals: dict = {}
    for v in sample(rng, len(products), int(traffic["check_visits"])):
        prod = products[v]
        subj = subjects[prod["subject"]]
        for step in traffic["visit"]:
            reference = getattr(cell.steps[step["step"]], "reference", None)
            if reference is None:
                continue
            for name, (count, per) in reference(subj, prod, rng, traffic, control).items():
                total = totals.get(name, (0, 0))
                totals[name] = (total[0] + count, total[1] + per)
    return {name: 1000.0 * count / max(per, 1) for name, (count, per) in totals.items()}


def accuracy(subj, labels, prec) -> np.ndarray:
    """The refit's accuracy over all test trials, per label row (B,) of ``labels`` (B, N)."""
    x, te, tr, lam = subj.x64, subj.te, subj.tr, subj.lam
    if subj.num_classes == 2:
        dv = refit.binary_dvals(x, labels.T, te, tr, lam, prec)
        return refit.fold_accuracy(dv, labels.T[te])
    preds, _ = refit.multiclass_predict(x, labels, te, tr, lam, subj.num_classes, prec)
    return (preds == labels[:, te]).mean(axis=(1, 2))


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
