#!/usr/bin/env python3
"""Smoke proof that the analytical-CV server runs on a TPU chip.

    python chip_smoke.py             # one chip: the served path, paper EEG size
    python chip_smoke.py --chips 4   # four chips: sharded Gram + sharded null only

One chip. Simulates one subject at the source paper's §2.13 shape with
``repro.data.eeg`` from a fixed seed — 787 trials, 380 channels, 200 ms
windowed features (P = 1900), float32 — once with C = 2 and once with
C = 3 classes, K = 10 folds, λ = 1. Both datasets register through
``Client``; the HTTP edge starts in this process (``EdgeThread``) and
seven workloads of four kinds go over loopback (``HTTPClient``) and
through the in-process ``Client``: ``cv`` as binary LDA with the default
bias adjustment (the ``foldsolve`` route), binary without it (the fused
``fold_eval`` route), ridge and 3-class LDA; a 1000-draw ``permutation``
test; an ``rsa`` workload (pairwise-contrast RDM, scored against model
RDMs the client builds, one of them with the ``pairdist`` kernel); and
a ``tune`` workload over a λ grid. Every answer must be a
result; wire and in-process answers must be equal; and they must agree
with a plain NumPy float64 refit per fold (the paper's "standard
approach"):

* decision values, RSA pair contrasts and the client's pairdist RDM
  within 1e-3 · max|ref|, predicted labels equal wherever |ref| exceeds
  that;
* 3-class predictions equal except at ties of the reference's nearest
  centroids (distance gap within 1e-3 of the largest distance);
* the first 32 permutation-null accuracies equal the reference on the same
  index draws within one test trial;
* every LOO score of the λ grid within 1e-3 of the reference's (the closed
  form of exact leave-one-out refits), and the same best λ.

Four chips. Builds a (data=2, model=2) mesh and a ``gram_impl="distributed"``
engine on it next to a one-device engine, runs only the feature-sharded
plan build and a 1000-draw permutation workload whose null shards over
``data``, and compares the sharded plan's hat matrix and null with the
one-device results on the same seed.

Times printed here are smoke times of single runs, not a benchmark. The
last line of standard output is ``{"ok": true, "device": {...}}``; any
failed check exits non-zero before it, and so does a run that finds no
TPU. The persistent compilation cache lives in ``JAX_COMPILATION_CACHE_DIR``
when set, else in ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import rsa  # noqa: E402
from repro.core import folds as foldlib  # noqa: E402
from repro.core import permutation as perm_lib  # noqa: E402
from repro.data import eeg  # noqa: E402
from repro.launch.compile_cache import setup_compilation_cache  # noqa: E402
from repro.serve import Client, CVEngine, EngineConfig, HTTPClient, Workload  # noqa: E402
from repro.serve.http import EdgeThread, assert_responses_equal  # noqa: E402

N_TRIALS = 787  # paper §2.13: one subject's trials
K_FOLDS = 10
LAM = 1.0
N_PERM = 1000
N_PERM_CHECKED = 32  # null draws compared with the float64 reference
REL_TOL = 1e-3  # decision values: |dev − ref| <= REL_TOL · max|ref|
RSA_PAIRS = ((0, 1), (0, 2), (1, 2))  # contrast column order, repro.rsa.condition_pairs(3)
SEED = 0


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def tpu_devices() -> list:
    """The TPU devices JAX sees; exits 1 when the first device is no TPU."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (jax.devices()[0].platform is "
            f"{devices[0].platform!r}); this smoke runs only on a TPU chip"
        )
    return devices


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------


def simulate(num_classes: int, n_trials: int, seed: int):
    """One simulated subject: (x float32 (N, 1900) on device, y int (N,))."""
    ds = eeg.simulate_subject(
        jax.random.PRNGKey(seed), n_trials=n_trials, num_classes=num_classes,
        dtype=jnp.float32,
    )
    return eeg.windowed_features(ds, 200.0), np.asarray(ds.y)


# ---------------------------------------------------------------------------
# Plain NumPy float64 reference: refit every fold from scratch
# ---------------------------------------------------------------------------


def ridge_refit(x, y, te, tr, lam):
    """Per-fold ridge refits with an unpenalised intercept, float64.

    x (N, P), y (N, B); te (K, m), tr (K, N−m). Each fold centres its own
    training data and solves the ridge system in its N_tr × N_tr dual
    form. Returns fits on the test rows (K, m, B) and training rows
    (K, N−m, B).
    """
    fit_te, fit_tr = [], []
    for te_k, tr_k in zip(te, tr):
        mu, ybar = x[tr_k].mean(0), y[tr_k].mean(0)
        xc = x[tr_k] - mu
        alpha = np.linalg.solve(xc @ xc.T + lam * np.eye(len(tr_k)), y[tr_k] - ybar)
        w = xc.T @ alpha
        fit_tr.append(xc @ w + ybar)
        fit_te.append((x[te_k] - mu) @ w + ybar)
    return np.stack(fit_te), np.stack(fit_tr)


def lda_dvals(fit_te, fit_tr, y, tr, adjust_bias):
    """Binary LDA decision values from the ridge fits of ±1 labels (K, m, B).

    With ``adjust_bias`` the regression bias becomes the LDA bias: minus
    the midpoint of the two classes' mean training decision values. A 0
    label (a sample outside an RSA pair contrast) is in neither class.
    """
    if not adjust_bias:
        return fit_te
    y_tr = y[tr]
    pos = (y_tr > 0).astype(np.float64)
    neg = (y_tr < 0).astype(np.float64)
    mu1 = (fit_tr * pos).sum(1) / pos.sum(1)
    mu2 = (fit_tr * neg).sum(1) / neg.sum(1)
    return fit_te - 0.5 * (mu1 + mu2)[:, None, :]


def multiclass_dists(fit_te, fit_tr, y1h, tr):
    """Squared distances (K, m, C) to the class centroids of multi-class LDA
    in its optimal-scoring form (Hastie et al. 1995), refit per fold."""
    eps = 1e-10
    out = []
    for f_te, f_tr, tr_k in zip(fit_te, fit_tr, tr):
        yk = y1h[tr_k]
        n_tr, c = yk.shape
        counts = yk.sum(0)
        dm = 1.0 / np.sqrt(counts / n_tr)
        ms = dm[:, None] * (f_tr.T @ yk / n_tr) * dm[None, :]
        evals, evecs = np.linalg.eigh(0.5 * (ms + ms.T))
        keep = np.arange(c - 2, -1, -1)  # drop the trivial α² = 1 pair
        a2 = np.clip(evals[keep], eps, 1.0 - eps)
        theta = dm[:, None] * evecs[:, keep] / (np.sqrt(n_tr) * np.sqrt(a2 * (1.0 - a2)))
        s_te, s_tr = f_te @ theta, f_tr @ theta
        centroids = (yk.T @ s_tr) / counts[:, None]
        out.append(((s_te[:, None, :] - centroids[None]) ** 2).sum(-1))
    return np.stack(out)


def pair_contrasts(dv, cols_te):
    """RSA "contrast" dissimilarity per pair column (B,): mean decision
    value on the pair's +1 test samples minus that on its −1 samples."""
    pos, neg = (cols_te > 0), (cols_te < 0)
    return (dv * pos).sum((0, 1)) / pos.sum((0, 1)) - (dv * neg).sum((0, 1)) / neg.sum((0, 1))


def loo_scores(x, y, lambdas):
    """Mean squared leave-one-out residual of ridge with an unpenalised
    intercept per λ, float64: ė_i = ê_i / (1 − H_ii), the closed form of
    N refits, with H = 11ᵀ/N + G_c (G_c + λI)⁻¹ built by direct solves."""
    xc = x - x.mean(0)
    g = xc @ xc.T
    n = len(y)
    out = []
    for lam in lambdas:
        h = 1.0 / n + np.linalg.solve(g + lam * np.eye(n), g)
        e = y - h @ y
        out.append(np.mean((e / (1.0 - np.diag(h))) ** 2))
    return np.array(out)


def fold_accuracy(dv, y_te):
    """Accuracy over all test trials per column (the engine's metric)."""
    return (np.where(dv >= 0, 1.0, -1.0) == np.sign(y_te)).mean(axis=(0, 1))


def check_values(name, got, ref):
    """Decision values within REL_TOL·max|ref|; signs equal above that."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    require(got.shape == ref.shape, f"{name}: shape {got.shape} != {ref.shape}")
    require(bool(np.isfinite(got).all()), f"{name}: non-finite values")
    scale = np.abs(ref).max()
    dev = np.abs(got - ref).max()
    clear = np.abs(ref) > REL_TOL * scale
    flips = int((np.sign(got) != np.sign(ref))[clear].sum())
    log(f"  {name}: max|dev−ref| = {dev:.3e} = {dev / scale:.3e}·max|ref| "
        f"(bar {REL_TOL:g}); label flips where |ref| > bar: {flips}")
    require(dev <= REL_TOL * scale, f"{name}: deviation {dev / scale:.3e}·max|ref|")
    require(flips == 0, f"{name}: {flips} predicted labels differ")
    return dev / scale


# ---------------------------------------------------------------------------
# One chip: the served path end to end
# ---------------------------------------------------------------------------


def one_chip(n_trials: int = N_TRIALS, n_perm: int = N_PERM, seed: int = SEED) -> dict:
    """Serve the seven workloads over HTTP and in process; check them.

    Returns a summary of the checks; raises on the first failure.
    """
    x2, y2_int = simulate(2, n_trials, seed)
    x3, y3 = simulate(3, n_trials, seed + 1)
    n = int(x2.shape[0])
    folds2 = foldlib.kfold(n, K_FOLDS, seed=seed)
    folds3 = foldlib.kfold(n, K_FOLDS, seed=seed + 1)
    rng = np.random.default_rng(seed)
    x2_64, x3_64 = np.asarray(x2, np.float64), np.asarray(x3, np.float64)
    y2 = np.where(y2_int == 0, -1.0, 1.0).astype(np.float32)
    w_true = rng.standard_normal(x2.shape[1]) / np.sqrt(x2.shape[1])
    y_ridge = (x2_64 @ w_true + 0.5 * rng.standard_normal(n)).astype(np.float32)
    # tune over λ = tr(G_c)/N · 10^[-2, 2]: below that the LOO denominators
    # 1 − H_ii cancel to a few float32 ulps, whatever the matmul precision
    scale = np.square(x2_64 - x2_64.mean(0)).sum() / n
    lambdas = (scale * np.logspace(-2, 2, 9)).astype(np.float32)
    models = jnp.stack([
        rsa.euclidean_rdm(rsa.condition_means(x3, jnp.asarray(y3), 3)),  # pairdist
        rsa.ring_rdm(3, jnp.float32),
    ])

    engine = CVEngine()
    client = Client(engine)
    h2 = client.register(x2, folds2, LAM)
    h3 = client.register(x3, folds3, LAM)
    kinds = ["cv/binary", "cv/binary-no-bias", "cv/ridge", "cv/multiclass",
             "permutation", "rsa", "tune"]
    workloads = [
        Workload(kind="cv", dataset=h2, y=y2),
        Workload(kind="cv", dataset=h2, y=y2, adjust_bias=False),
        Workload(kind="cv", dataset=h2, y=y_ridge, estimator="ridge"),
        Workload(kind="cv", dataset=h3, y=y3.astype(np.int32), estimator="multiclass",
                 num_classes=3),
        Workload(kind="permutation", dataset=h2, y=y2, n_perm=n_perm, seed=seed + 11),
        Workload(kind="rsa", dataset=h3, y=y3.astype(np.int32), num_classes=3,
                 dissimilarity="contrast", model_rdms=models, n_perm=64, seed=seed + 12),
        Workload(kind="tune", x=x2, y=y_ridge, lambdas=lambdas),
    ]

    with EdgeThread(engine) as edge, HTTPClient(edge.url) as wire:
        t0 = time.perf_counter()
        over_wire = wire.gather(workloads)
        t_cold = time.perf_counter() - t0
        in_process = client.gather(workloads)
        compiles = engine.compile_count()
        t0 = time.perf_counter()
        replay_wire = wire.gather(workloads)
        replay_local = client.gather(workloads)
        t_warm = time.perf_counter() - t0
        warm_compiles = engine.compile_count() - compiles

    for kind, a, b, c, d in zip(kinds, over_wire, in_process, replay_wire, replay_local):
        for label, resp in (("http", a), ("client", b), ("http replay", c), ("client replay", d)):
            require(not isinstance(resp, Exception), f"{kind} via {label}: {resp!r}")
        assert_responses_equal(a, b, kind)
        assert_responses_equal(c, b, kind)
        assert_responses_equal(d, b, kind)
    log(f"every workload kind answered over HTTP and Client, equal both ways: "
        f"{', '.join(kinds)}")

    x_dtype = engine.dataset_record(h2).x.dtype
    h_dtype = engine.resolve(h2)[1].h.dtype
    log(f"registered x dtype {x_dtype}, plan h dtype {h_dtype}")
    require(x_dtype == jnp.float32 and h_dtype == jnp.float32, "device dtypes are not float32")
    log(f"compiled programs after the cold pass: {compiles}; on warm replay: {warm_compiles}")
    require(warm_compiles == 0, f"warm replay compiled {warm_compiles} programs")
    log(f"smoke times (one run each, not a benchmark): cold {t_cold:.3f}s for "
        f"{len(workloads)} workloads over HTTP, plan builds and compiles included; "
        f"warm replay {t_warm:.3f}s for HTTP + Client")

    # -- the float64 standard approach ------------------------------------
    te2, tr2 = np.asarray(folds2.te_idx), np.asarray(folds2.tr_idx)
    te3, tr3 = np.asarray(folds3.te_idx), np.asarray(folds3.tr_idx)
    draws = np.asarray(perm_lib.permutation_indices(
        jax.random.PRNGKey(seed + 11), n, N_PERM_CHECKED))
    cols = np.concatenate(
        [y2[:, None], y_ridge[:, None], y2[draws].T], axis=1).astype(np.float64)
    fit_te, fit_tr = ridge_refit(x2_64, cols, te2, tr2, LAM)
    y1h = np.eye(3)[y3]
    mc_te, mc_tr = ridge_refit(x3_64, y1h, te3, tr3, LAM)

    dv_bias = lda_dvals(fit_te[..., :1], fit_tr[..., :1], cols[:, :1], tr2, True)[..., 0]
    devs = {
        "cv/binary": check_values("cv/binary (foldsolve route)", in_process[0].values, dv_bias),
        "cv/binary-no-bias": check_values(
            "cv/binary-no-bias (fold_eval route)", in_process[1].values, fit_te[..., 0]),
        "cv/ridge": check_values("cv/ridge", in_process[2].values, fit_te[..., 1]),
    }

    d2 = multiclass_dists(mc_te, mc_tr, y1h, tr3)
    srt = np.sort(d2, axis=-1)
    ties = (srt[..., 1] - srt[..., 0]) <= REL_TOL * d2.max()
    pred = np.asarray(in_process[3].values)
    wrong = int(((pred != d2.argmin(-1)) & ~ties).sum())
    log(f"  cv/multiclass: {wrong} predictions differ from the reference "
        f"outside {int(ties.sum())} near-ties")
    require(wrong == 0, f"cv/multiclass: {wrong} predictions differ")

    perm = in_process[4]
    y_perm = cols[:, 2:]
    dv_perm = lda_dvals(fit_te[..., 2:], fit_tr[..., 2:], y_perm, tr2, True)
    null_ref = fold_accuracy(dv_perm, y_perm[te2])
    obs_ref = fold_accuracy(dv_bias[..., None], cols[te2, :1])[0]
    trial = 1.0 / te2.size
    null_dev = np.abs(np.asarray(perm.null)[:N_PERM_CHECKED] - null_ref).max()
    obs_dev = abs(float(perm.observed) - obs_ref)
    log(f"  permutation: {np.asarray(perm.null).size} null draws, p = {float(perm.p):.4f}; "
        f"first {N_PERM_CHECKED} null accuracies max|dev−ref| = {null_dev:.4g}, "
        f"observed |dev−ref| = {obs_dev:.4g} (one trial = {trial:.4g})")
    require(np.asarray(perm.null).size == n_perm, "permutation null has the wrong size")
    require(null_dev <= trial + 1e-6 and obs_dev <= trial + 1e-6,
            "permutation accuracies differ by more than one trial")

    rdm_resp = in_process[5]
    pair_cols = np.stack([y1h[:, a] - y1h[:, b] for a, b in RSA_PAIRS], axis=1)
    pr_te, pr_tr = ridge_refit(x3_64, pair_cols, te3, tr3, LAM)
    pair_ref = pair_contrasts(lda_dvals(pr_te, pr_tr, pair_cols, tr3, True), pair_cols[te3])
    rdm_ref = np.zeros((3, 3))
    for (a, b), v in zip(RSA_PAIRS, pair_ref):
        rdm_ref[a, b] = rdm_ref[b, a] = v
    devs["rsa"] = check_values("rsa empirical RDM (pair contrasts)", rdm_resp.rdm, rdm_ref)
    require(bool(np.isfinite(np.asarray(rdm_resp.model_scores)).all()),
            "rsa: non-finite model scores")
    log(f"  rsa model scores {np.asarray(rdm_resp.model_scores)}")
    mu = np.stack([x3_64[y3 == c].mean(0) for c in range(3)])
    pd_ref = ((mu[:, None, :] - mu[None]) ** 2).sum(-1)
    devs["pairdist"] = check_values("client model RDM (pairdist kernel)", models[0], pd_ref)

    tune = in_process[6].result
    lambdas = np.asarray(tune.lambdas, np.float64)
    loo_ref = loo_scores(x2_64, y_ridge.astype(np.float64), lambdas)
    loo_got = np.asarray(tune.scores, np.float64)
    tune_dev = float((np.abs(loo_got - loo_ref) / loo_ref).max())
    best = int(np.argmin(loo_got))
    best_ref = int(np.argmin(loo_ref))
    log(f"  tune: {lambdas.size} λ, max per-λ |dev−ref|/ref = {tune_dev:.3e} (bar {REL_TOL:g}); "
        f"best λ index {best} (reference {best_ref})")
    require(tune_dev <= REL_TOL, f"tune: LOO scores deviate by {tune_dev:.3e} of the reference")
    require(best == best_ref or loo_ref[best] <= (1 + REL_TOL) * loo_ref[best_ref],
            f"tune: best λ index {best} != reference {best_ref}")
    devs["tune"] = tune_dev

    stats = jax.devices()[0].memory_stats() or {}
    log(f"device peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    return {"cold_s": t_cold, "warm_s": t_warm, "compiles": compiles,
            "rel_dev": devs, "null_dev": float(null_dev)}


# ---------------------------------------------------------------------------
# Four chips: feature-sharded Gram + data-sharded permutation null
# ---------------------------------------------------------------------------


def four_chip(n_trials: int = N_TRIALS, n_perm: int = N_PERM, seed: int = SEED) -> dict:
    """Sharded plan build + sharded null vs the one-device engine."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import sharded_null_from_plan

    require(len(jax.devices()) == 4, f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    x, y_int = simulate(2, n_trials, seed)
    y = np.where(y_int == 0, -1.0, 1.0).astype(np.float32)
    n = int(x.shape[0])
    folds = foldlib.kfold(n, K_FOLDS, seed=seed)
    x_mesh = jax.device_put(x, NamedSharding(mesh, P(None, "model")))

    one = CVEngine()
    sharded = CVEngine(EngineConfig(gram_impl="distributed", mesh=mesh))
    h_one = one.register(x, folds, LAM)
    h_mesh = sharded.register(x_mesh, folds, LAM)

    t0 = time.perf_counter()
    plan_mesh = jax.block_until_ready(sharded.resolve(h_mesh)[1])
    t_plan = time.perf_counter() - t0
    plan_one = one.resolve(h_one)[1]
    dh = float(np.abs(np.asarray(plan_mesh.h, np.float64) - np.asarray(plan_one.h)).max())
    log(f"x sharding: {x_mesh.sharding.spec} over "
        f"{sorted(d.id for d in x_mesh.sharding.device_set)}; "
        f"plan h sharding: {plan_mesh.h.sharding}")
    log(f"sharded plan build (smoke time, compile included): {t_plan:.3f}s; "
        f"max|h_sharded − h_one_device| = {dh:.3e}")

    w = dict(kind="permutation", y=y, n_perm=n_perm, seed=seed + 11)
    r_mesh = Client(sharded).submit(Workload(dataset=h_mesh, **w))
    r_one = Client(one).submit(Workload(dataset=h_one, **w))
    # the engine's null, before it is gathered for slicing: T over "data"
    null_shards = sharded_null_from_plan(
        plan_mesh, perm_lib.permutation_draws(jax.random.PRNGKey(seed), jnp.asarray(y), 8),
        mesh)
    trial = 1.0 / np.asarray(folds.te_idx).size
    dnull = float(np.abs(np.asarray(r_mesh.null) - np.asarray(r_one.null)).max())
    dobs = abs(float(r_mesh.observed) - float(r_one.observed))
    log(f"null sharding: {null_shards.sharding.spec} over "
        f"{sorted(d.id for d in null_shards.sharding.device_set)}")
    log(f"permutation null ({np.asarray(r_mesh.null).size} draws): "
        f"max|sharded − one-device| = {dnull:.4g}, observed diff {dobs:.4g} "
        f"(one trial = {trial:.4g}); p = {float(r_mesh.p):.4f} vs {float(r_one.p):.4f}")
    for d in jax.devices():
        stats = d.memory_stats() or {}
        log(f"  device {d.id}: bytes_in_use {stats.get('bytes_in_use', 'not reported')}, "
            f"peak {stats.get('peak_bytes_in_use', 'not reported')}")

    require(len(x_mesh.sharding.device_set) == 4, "x does not span the four devices")
    require(len(plan_mesh.h.sharding.device_set) == 4, "the sharded plan is not on all devices")
    require(null_shards.sharding.spec == P("data") and len(null_shards.sharding.device_set) == 4,
            "the null is not sharded over the data axis")
    require(dh <= 1e-4, f"sharded hat matrix differs by {dh:.3e}")
    require(np.asarray(r_mesh.null).size == n_perm, "permutation null has the wrong size")
    require(dnull <= trial + 1e-6 and dobs <= trial + 1e-6,
            "sharded null differs from the one-device null by more than one trial")
    return {"plan_s": t_plan, "dh": dh, "dnull": dnull}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): the served path on one chip; 4: only the "
                    "mesh path (sharded Gram + sharded null) on four chips")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    devices = tpu_devices()
    dev = devices[0]
    log(f"device: platform {dev.platform}, kind {dev.device_kind}, count {len(devices)}")
    log(f"compilation cache: {setup_compilation_cache()}")
    if args.chips == 4:
        four_chip()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
