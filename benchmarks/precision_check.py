#!/usr/bin/env python3
"""Matmul precision on the chip: accuracy against float64, and its cost.

    python benchmarks/precision_check.py [--n-trials 787] [--reps 20]

Runs the binary-LDA analytical CV of ``chip_smoke.py`` (paper §2.13 EEG
shape, 787 × 1900 float32, K = 10, λ = 1) as one plain XLA program per
contraction precision — ``DEFAULT`` (one bfloat16 pass on a TPU), ``HIGH``
(three) and ``HIGHEST`` (six, full float32) — and reports, per precision:

* the centred Gram's and the decision values' largest deviation from the
  NumPy float64 per-fold refit, relative to the reference's largest value
  (chip_smoke's bar is 1e-3), and the predicted labels that flip;
* warm device times (median of ``--reps`` calls) of the plan build and of
  one evaluation at B = 1 and B = 1000 label columns.

The served code fixes ``HIGHEST`` for float32 operands
(``repro.kernels.common.dot_precision``); this script is the
counterfactual that shows what the lower settings would give. Its programs
mirror the XLA route of ``repro.core.fastcv`` rather than call it, so the
precision can vary. Off a TPU every setting is full float32 and the rows
agree. The last line of standard output is one JSON object with the rows.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.scipy.linalg import cho_factor, cho_solve  # noqa: E402

import chip_smoke as cs  # noqa: E402
from repro.core import folds as foldlib  # noqa: E402
from repro.kernels.gram.ops import centered_gram  # noqa: E402

PRECISIONS = {
    "DEFAULT": jax.lax.Precision.DEFAULT,
    "HIGH": jax.lax.Precision.HIGH,
    "HIGHEST": jax.lax.Precision.HIGHEST,
}
BATCHES = (1, 1000)


def make_plan(prec):
    @jax.jit
    def plan(x, te, tr):
        xc = x - x.mean(0)
        g = jnp.matmul(xc, xc.T, precision=prec)
        n = x.shape[0]
        h = cho_solve(cho_factor(g + cs.LAM * jnp.eye(n, dtype=x.dtype)), g)
        h = 0.5 * (h + h.T) + 1.0 / n
        ih = jnp.eye(te.shape[1], dtype=x.dtype)[None] - h[te[:, :, None], te[:, None, :]]
        return g, h, ih, h[tr[:, :, None], te[:, None, :]]

    return plan


def make_eval(prec):
    @jax.jit
    def evaluate(h, ih, h_tr_te, te, tr, y):
        e = y - jnp.matmul(h, y, precision=prec)
        t = jnp.linalg.solve(ih, e[te])
        y_dot_tr = y[tr] - e[tr] - jnp.einsum("knm,kmb->knb", h_tr_te, t, precision=prec)
        pos = (y[tr] > 0).astype(y.dtype)
        neg = 1.0 - pos
        mu1 = (y_dot_tr * pos).sum(1) / pos.sum(1)
        mu2 = (y_dot_tr * neg).sum(1) / neg.sum(1)
        return y[te] - t - 0.5 * (mu1 + mu2)[:, None, :]

    return evaluate


def warm_ms(fn, *args, reps: int) -> float:
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(times)


def run(n_trials: int = cs.N_TRIALS, reps: int = 20, seed: int = cs.SEED) -> list:
    x, y_int = cs.simulate(2, n_trials, seed)
    n = int(x.shape[0])
    y = np.where(y_int == 0, -1.0, 1.0)
    folds = foldlib.kfold(n, cs.K_FOLDS, seed=seed)
    te, tr = jnp.asarray(folds.te_idx), jnp.asarray(folds.tr_idx)
    x64 = np.asarray(x, np.float64)
    xc64 = x64 - x64.mean(0)
    g_ref = xc64 @ xc64.T
    fit_te, fit_tr = cs.ridge_refit(x64, y[:, None], np.asarray(te), np.asarray(tr), cs.LAM)
    dv_ref = cs.lda_dvals(fit_te, fit_tr, y[:, None], np.asarray(tr), True)[..., 0]
    rng = np.random.default_rng(seed)
    batches = {b: jnp.asarray(np.stack([rng.permutation(y) for _ in range(b)], 1), jnp.float32)
               for b in BATCHES}
    batches[1] = jnp.asarray(y[:, None], jnp.float32)

    rows = []
    for name, prec in PRECISIONS.items():
        plan, evaluate = make_plan(prec), make_eval(prec)
        g, h, ih, h_tr_te = plan(x, te, tr)
        dv = np.asarray(evaluate(h, ih, h_tr_te, te, tr, batches[1])[..., 0], np.float64)
        row = {
            "precision": name,
            "gram_rel_err": float(np.abs(np.asarray(g, np.float64) - g_ref).max()
                                  / np.abs(g_ref).max()),
            "dvals_rel_dev": float(np.abs(dv - dv_ref).max() / np.abs(dv_ref).max()),
            "label_flips": int((np.sign(dv) != np.sign(dv_ref)).sum()),
            "plan_ms": warm_ms(plan, x, te, tr, reps=reps),
        }
        for b, yb in batches.items():
            row[f"eval_ms_b{b}"] = warm_ms(evaluate, h, ih, h_tr_te, te, tr, yb, reps=reps)
        rows.append(row)
        print(f"[precision_check] {row}", flush=True)
    gp = np.asarray(centered_gram(x), np.float64)
    pallas = float(np.abs(gp - g_ref).max() / np.abs(g_ref).max())
    print(f"[precision_check] served Gram (repro.kernels.gram, HIGHEST) rel err {pallas:.3e}")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n-trials", type=int, default=cs.N_TRIALS)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    print(f"[precision_check] device {dev.platform} {dev.device_kind}", flush=True)
    rows = run(args.n_trials, args.reps)
    print(json.dumps({"device": dev.device_kind, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
