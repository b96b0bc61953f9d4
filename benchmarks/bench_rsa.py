"""RSA serving benchmark: cold-plan vs warm-cache RDM requests, contrast
throughput, and the Pallas pairdist kernel vs its XLA oracle.

The RSA economics are the paper's §4.2 pitch operationalised: all
C(C−1)/2 pairwise contrasts of an RDM are one label batch against the
cached plan — a cold request pays the O(N²P) Gram + factorisation + jit
compile, a warm one only the O(K·m²·B) fold solves plus tiny model
scoring, through already-compiled programs.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import row, timeit
from repro import rsa
from repro.core import folds as foldlib
from repro.data import synthetic
from repro.serve import CVEngine, DatasetSpec, Workload, run_workloads


def run(fast: bool = False):
    rows = []
    n, p, c, t_perm = (96, 512, 6, 32) if fast else (240, 4096, 12, 128)
    k = 6
    lam = 1.0

    x, y_cond = synthetic.make_classification(
        jax.random.PRNGKey(0), n, p, num_classes=c, class_sep=2.0
    )
    f = foldlib.stratified_kfold(y_cond, k, seed=0)
    spec = DatasetSpec(x, f, lam)
    mu = rsa.condition_means(x, y_cond, c)
    models = jnp.stack([rsa.euclidean_rdm(mu), rsa.ring_rdm(c)])
    req = Workload(
        kind="rsa", dataset=spec, y=y_cond, num_classes=c, model_rdms=models, n_perm=t_perm, seed=0
    )

    # -- cold: fresh engine; plan build + compile + eval -------------------
    engine = CVEngine()
    t0 = time.perf_counter()
    jax.block_until_ready(run_workloads(engine, [req])[0].rdm)
    t_cold = time.perf_counter() - t0
    rows.append(
        row(f"bench_rsa_cold_N{n}_P{p}_C{c}", t_cold, "plan build + compile + RDM + model scoring")
    )

    # -- warm: cached plan, compiled programs ------------------------------
    compiles_warm = engine.compile_count()

    def warm_once():
        return run_workloads(engine, [req])[0].rdm

    t_warm = timeit(warm_once, warmup=1, repeats=5)
    recompiles = engine.compile_count() - compiles_warm
    rows.append(
        row(
            f"bench_rsa_warm_N{n}_P{p}_C{c}",
            t_warm,
            f"speedup={t_cold / t_warm:.0f}x recompiles={recompiles}",
        )
    )

    # -- coalesced RSA batches: requests/s vs batch size -------------------
    for bs in (1, 4, 16):
        reqs = [
            Workload(
                kind="rsa",
                dataset=spec,
                y=y_cond,
                num_classes=c,
                model_rdms=models,
                n_perm=t_perm,
                seed=s,
            )
            for s in range(bs)
        ]

        def rsa_batch():
            return [r.rdm for r in run_workloads(engine, reqs)]

        secs = timeit(rsa_batch, warmup=1, repeats=5)
        rows.append(row(f"bench_rsa_warm_batch{bs}_N{n}_P{p}_C{c}", secs, f"{bs / secs:.0f} req/s"))

    # -- pairdist kernel (interpret off-TPU) vs the XLA oracle -------------
    cc = 32 if fast else 64
    patterns = jax.random.normal(jax.random.PRNGKey(1), (cc, p), jnp.float64)
    t_xla = timeit(lambda: rsa.euclidean_rdm(patterns, impl="xla"), warmup=1, repeats=5)
    rows.append(row(f"bench_rsa_pairdist_xla_C{cc}_P{p}", t_xla, "jnp oracle"))
    t_pal = timeit(lambda: rsa.euclidean_rdm(patterns, impl="pallas"), warmup=1, repeats=3)
    rows.append(
        row(
            f"bench_rsa_pairdist_pallas_C{cc}_P{p}",
            t_pal,
            "interpret-mode off-TPU; compiled on real TPUs",
        )
    )
    return rows
