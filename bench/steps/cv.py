"""``cv``: one cross-validation workload on the subject's own labels.

The product is the served values (``cv``); the check compares every test
trial's multi-class prediction with the float64 refit's.
"""

import numpy as np

from bench.reference import refit


def run(client, engine, subj, step, seed) -> dict:
    from repro.serve import Workload

    resp = client.submit(Workload(kind="cv", **subj.workload_args()))
    return {"cv": np.asarray(resp.values), "labels": 1}


def _predictions(subj, prec):
    preds, _ = refit.multiclass_predict(subj.x64, subj.y[None], subj.te, subj.tr, subj.lam,
                                        subj.num_classes, prec)
    return preds[0]


def reference(subj, prod, rng, traffic, control) -> dict:
    """Test trials whose predicted class differs from the refit's, per label vector.

    With ``control``, the reference at that precision predicts in the
    program's place.
    """
    got = prod["cv"] if control is None else _predictions(subj, control)
    flips = int((np.asarray(got) != _predictions(subj, refit.FLOAT64)).sum())
    return {"flips_per_1k": (flips, 1)}
