"""Multi-device worker script run by tests/test_distributed.py in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8.

Each check prints 'PASS <name>' on success; the pytest wrapper asserts on
the output. Separated from the test module so the 8-device XLA flag never
leaks into the main test process (smoke tests must see 1 device).
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

jax.config.update("jax_enable_x64", True)


def check(name, cond):
    print(("PASS " if cond else "FAIL ") + name)
    if not cond:
        sys.exit(1)


def main():
    assert len(jax.devices()) == 8, jax.devices()
    mesh = jax.make_mesh((2, 4), ("data", "model"))

    from repro.core import distributed as D
    from repro.core import fastcv, folds as foldlib, permutation
    from repro.data import synthetic

    # ---- feature-sharded Gram == local Gram ------------------------------
    x, yc = synthetic.make_classification(jax.random.PRNGKey(0), 48, 64)
    y = jnp.where(yc == 0, -1.0, 1.0)
    xs = jax.device_put(x, NamedSharding(mesh, P(None, "model")))
    g_dist = D.distributed_gram(xs, mesh)
    xc = x - jnp.mean(x, axis=0, keepdims=True)
    check("distributed_gram",
          np.allclose(np.asarray(g_dist), np.asarray(xc @ xc.T), atol=1e-8))
    # a caller's jit under its own set_mesh: the mesh is already ambient
    with jax.set_mesh(mesh):
        g_jit = jax.jit(lambda v: D.distributed_gram(v, mesh))(xs)
    check("distributed_gram_under_jit",
          np.array_equal(np.asarray(g_jit), np.asarray(g_dist)))

    # ---- distributed hat matrix == single-device hat matrix --------------
    h_dist = D.distributed_hat_matrix(xs, 1.0, mesh)
    h_ref = fastcv.hat_matrix_dual(x, 1.0)
    check("distributed_hat",
          np.allclose(np.asarray(h_dist), np.asarray(h_ref), atol=1e-8))

    # ---- permutation-sharded null == single-device null ------------------
    f = foldlib.kfold(48, 4, seed=1)
    key = jax.random.PRNGKey(7)
    res_d = D.distributed_permutation_binary(
        xs, y, f, 1.0, n_perm=16, key=key, mesh=mesh)
    res_s = permutation.analytical_permutation_binary(
        x, y, f, 1.0, n_perm=16, key=key, chunk=16)
    check("distributed_permutation_null",
          np.allclose(np.asarray(res_d.null), np.asarray(res_s.null),
                      atol=1e-10))
    check("distributed_permutation_obs",
          abs(float(res_d.observed) - float(res_s.observed)) < 1e-10)

    # ---- searchlight sharding ---------------------------------------------
    keys = jax.random.split(jax.random.PRNGKey(3), 8)
    xs_many = jnp.stack([
        synthetic.make_classification(k, 48, 32, class_sep=3.0)[0]
        for k in keys])
    xs_many = jax.device_put(xs_many, NamedSharding(mesh, P(("data",))))
    acc = D.searchlight_cv(xs_many, y, f, 1.0, mesh,
                           problem_axes=("data",))
    check("searchlight_shape", acc.shape == (8,))
    check("searchlight_finite", bool(np.isfinite(np.asarray(acc)).all()))

    # ---- sharded train step runs and matches unsharded loss ---------------
    # on the meshes the LM launchers build (Auto axes, repro.launch.mesh)
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(model_axis=4)
    from repro.configs.base import get_config
    from repro.launch import sharding as sh
    from repro.optim import optimizer as O
    from repro.train import steps
    from repro.models import model as M

    cfg = get_config("gemma2-2b", smoke=True)
    opt_cfg = O.AdamWConfig(lr_peak=1e-3, warmup_steps=1, total_steps=5)
    params, opt_state = steps.init_train_state(jax.random.PRNGKey(0), cfg,
                                               opt_cfg)
    kt = jax.random.PRNGKey(5)
    batch = {"tokens": jax.random.randint(kt, (4, 16), 0, cfg.vocab_size),
             "labels": jax.random.randint(kt, (4, 16), 0, cfg.vocab_size)}

    loss_ref = float(M.loss_fn(params, batch, cfg)[0])

    p_sh = sh.param_sharding_tree(params, mesh)
    params_s = jax.device_put(params, p_sh)
    opt_s = jax.device_put(opt_state, jax.tree.map(
        lambda _: NamedSharding(mesh, P()), opt_state))
    batch_s = jax.device_put(batch, NamedSharding(mesh, P("data", None)))

    with sh.axis_ctx(mesh):
        step_fn = jax.jit(steps.make_train_step(cfg, opt_cfg))
        new_p, new_o, metrics = step_fn(params_s, opt_s, batch_s)
    loss_sharded = float(metrics["loss"])
    check("sharded_train_loss_matches",
          abs(loss_sharded - loss_ref) < 1e-3 * max(1.0, abs(loss_ref)))
    check("sharded_train_finite", np.isfinite(loss_sharded))

    # ---- elastic checkpoint: save on (2,4), restore on (4,2) --------------
    from repro.train import checkpoint as ckpt
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        ckpt.save(td, 1, {"params": new_p})
        mesh2 = make_host_mesh(model_axis=2)
        p_sh2 = sh.param_sharding_tree(params, mesh2)
        restored, _ = ckpt.restore(td, 1, {"params": params},
                                   {"params": p_sh2})
        same = jax.tree.all(jax.tree.map(
            lambda a, b: jnp.allclose(a.astype(jnp.float32),
                                      b.astype(jnp.float32), atol=1e-6),
            restored["params"], new_p))
        check("elastic_restore_values", bool(same))
        one = jax.tree.leaves(restored["params"])[0]
        check("elastic_restore_mesh",
              one.sharding.mesh.shape["data"] == 4)

    print("ALL_OK")


if __name__ == "__main__":
    main()
