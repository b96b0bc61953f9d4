"""Four-device worker run by tests/test_mesh_engine.py in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4.

Drives a (2, 2) mesh engine ({"data": 2, "model": 2}, features over
"model", draws over both axes) through ``Client`` → ``permutation_binary``
beside a one-device engine, and prints one JSON object of readings on its
last line; the pytest wrapper asserts on them. Float64, as the suite runs.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

jax.config.update("jax_enable_x64", True)
jax.config.update("jax_numpy_rank_promotion", "raise")

N, P_FEAT, K, LAM, N_PERM, SEED = 60, 128, 5, 1.0, 50, 7
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def main():
    from bench.reference import refit
    from bench.reference.draws import permutation_indices
    from repro.core import folds as foldlib
    from repro.serve import CVEngine, Client, EngineConfig, Workload
    from repro.serve.workload import stream_workload

    assert len(jax.devices()) == 4, jax.devices()
    rng = np.random.default_rng(0)
    y = np.where(np.arange(N) % 2 == 0, -1.0, 1.0)
    x = rng.standard_normal((N, P_FEAT)) + 0.4 * y[:, None] * rng.standard_normal(P_FEAT)
    folds = foldlib.kfold(N, K, seed=1)
    out = {}

    # the configuration as a JSON file states it
    config = EngineConfig(mesh={"data": 2, "model": 2}, gram_impl="distributed",
                          perm_axes=["data", "model"])
    out["mapping_mesh"] = {"is_mesh": isinstance(config.mesh, Mesh),
                           "shape": dict(config.mesh.shape),
                           "devices": len(config.mesh.devices.flat),
                           "perm_axes": list(config.perm_axes),
                           "perm_axes_tuple": isinstance(config.perm_axes, tuple)}
    mesh_engine, one_engine = CVEngine(config), CVEngine()
    mesh_client, one_client = Client(mesh_engine), Client(one_engine)
    h_mesh = mesh_client.register(x, (folds.te_idx, folds.tr_idx), LAM)
    h_one = one_client.register(x, (folds.te_idx, folds.tr_idx), LAM)
    x_sh = mesh_engine.dataset_record(h_mesh).x.sharding
    out["x_layout"] = {"spec": list(x_sh.spec), "devices": len(x_sh.device_set)}

    def analysis(client, handle, seed):
        return client.submit(Workload(kind="permutation", dataset=handle, y=y,
                                      n_perm=N_PERM, seed=seed))

    r_mesh = analysis(mesh_client, h_mesh, SEED)
    r_one = analysis(one_client, h_one, SEED)
    # the test's one program hands its packed result back replicated
    program = mesh_engine._perm_tests[("mesh", "binary", "accuracy", True, 0)]
    _, plan = mesh_engine.resolve(h_mesh)
    packed = program(plan, y, jax.random.PRNGKey(SEED), np.int32(N_PERM), 64)
    out["null"] = {"mesh": np.asarray(r_mesh.null).tolist(),
                   "one": np.asarray(r_one.null).tolist(),
                   "observed": [float(r_mesh.observed), float(r_one.observed)],
                   "p": [float(r_mesh.p), float(r_one.p)],
                   "null_devices": len(packed.sharding.device_set),
                   "host": all(isinstance(v, (np.ndarray, np.generic))
                               for v in (r_mesh.null, r_mesh.observed, r_mesh.p))}

    # the float64 refit per fold, on the observed labels and sampled draws
    draws = np.sort(rng.choice(N_PERM, 16, replace=False))
    perms = permutation_indices(SEED, N, N_PERM)[draws]
    cols = np.stack([y] + list(y[perms]), axis=1)  # (N, 1 + 16)
    te, tr = np.asarray(folds.te_idx), np.asarray(folds.tr_idx)
    acc = refit.fold_accuracy(refit.binary_dvals(x, cols, te, tr, LAM), cols[te])
    got = [float(r_mesh.observed)] + [float(v) for v in np.asarray(r_mesh.null)[draws]]
    out["refit"] = {"reference": acc.tolist(), "program": got}

    # a second analysis of the same shape makes no program
    made = []

    def on_event(event, duration, **kw):
        if event == _COMPILE_EVENT:
            made.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    analysis(mesh_client, h_mesh, SEED + 1)
    jax.monitoring.unregister_event_duration_listener(on_event)
    out["second_analysis_programs"] = len(made)

    # a chunk that is no whole number of shards: padded, then cut back
    chunk = y[permutation_indices(SEED, N, 30)]
    part = mesh_engine.null_binary(plan, jax.numpy.asarray(chunk))
    out["chunk"] = {"size": int(part.shape[0]),
                    "equal": bool(np.array_equal(np.asarray(part), np.asarray(r_mesh.null)[:30]))}
    draws_c = mesh_engine.metrics.get("null_draws_total")
    pads_c = mesh_engine.metrics.get("null_pad_draws_total")
    out["counters"] = {"mesh_draws": draws_c.value(path="mesh"),
                       "mesh_pads": pads_c.value(path="mesh"),
                       "local_draws": one_engine.metrics.get("null_draws_total").value(path="local"),
                       "local_pads": one_engine.metrics.get("null_pad_draws_total").value(path="local"),
                       "labels_evaluated": mesh_engine.labels_evaluated}

    # the same test streamed in chunks on the mesh (the chunked path),
    # after the counters are read
    done = list(stream_workload(mesh_engine, Workload(kind="permutation", dataset=h_mesh, y=y,
                                                      n_perm=N_PERM, seed=SEED), chunk=16))[-1]
    streamed = done.payload
    out["stream"] = {"null": np.asarray(streamed.null).tolist(),
                     "observed": float(streamed.observed), "p": float(streamed.p)}

    # a live Mesh still works, with the default perm_axes ("data",)
    live = jax.make_mesh((2, 2), ("data", "model"))
    live_engine = CVEngine(EngineConfig(mesh=live, gram_impl="distributed"))
    live_client = Client(live_engine)
    r_live = analysis(live_client, live_client.register(x, (folds.te_idx, folds.tr_idx), LAM),
                      SEED)
    out["live_mesh"] = {"same_mesh": live_engine.config.mesh is live,
                        "null": np.asarray(r_live.null).tolist()}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
