"""Distributed analytical CV: shard_map building blocks (DESIGN.md §5).

The paper's workload decomposes onto the mesh as:

  * feature axis ("model"): the O(N²P) Gram reduction — each shard computes
    a partial X_c X_cᵀ over its feature slice, one ``psum`` combines them.
    This is the only cross-"model" collective in the whole CV pipeline.
  * permutation axis ("data"): Algorithm 1/2's T permutations are
    embarrassingly parallel given H — each shard evaluates its slice
    against the replicated (N×N) hat matrix and fold factors.
  * problem axis ("pod"): searchlights / time points / RSA pairs — fully
    independent CV problems, zero cross-pod traffic after data layout.

N is bounded by the paper's own premise (P ≫ N, N ≤ ~10⁴), so H and the
fold factors replicate comfortably; everything that scales (features,
permutations, problems) is sharded.
"""

from __future__ import annotations

import contextlib
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import fastcv
from repro.core.folds import Folds
from repro.kernels.common import dot_precision

__all__ = [
    "distributed_gram",
    "distributed_hat_matrix",
    "distributed_permutation_binary",
    "mesh_null_body",
    "mesh_null_program",
    "mesh_permutation_program",
    "whole_shards",
    "sharded_null_from_plan",
    "replicated",
    "sharded_problems",
    "searchlight_cv",
]


def _ambient(mesh: Mesh):
    """Make ``mesh`` the ambient mesh around a ``shard_map`` call.

    Gathers inside the mapped bodies place their indices on the ambient
    mesh. ``jax.set_mesh`` works only outside ``jit``; a caller that jits
    these functions sets the mesh around its own call, and then it is
    already ambient here.
    """
    if jax.sharding.get_abstract_mesh() == mesh.abstract_mesh:
        return contextlib.nullcontext()
    return jax.set_mesh(mesh)


def distributed_gram(x: jax.Array, mesh: Mesh, *, center: bool = True,
                     feature_axis: str = "model") -> jax.Array:
    """G_c = X_c X_cᵀ with X sharded (replicated_N, features/"model").

    Local partial Gram per feature shard + one psum over the feature axis,
    in one jitted program (``jit__mesh_gram``) per shape.
    """
    with _ambient(mesh):
        return _mesh_gram(x, mesh=mesh, center=center, feature_axis=feature_axis)


@partial(jax.jit, static_argnames=("mesh", "center", "feature_axis"))
def _mesh_gram(x, *, mesh, center, feature_axis):
    if center:
        x = x - jnp.mean(x, axis=0, keepdims=True)

    def local_gram(x_shard):
        g = jnp.matmul(x_shard, x_shard.T, precision=dot_precision(x_shard.dtype))
        return jax.lax.psum(g, feature_axis)

    return jax.shard_map(local_gram, mesh=mesh, in_specs=P(None, feature_axis),
                         out_specs=P(None, None))(x)


def distributed_hat_matrix(x: jax.Array, lam: float, mesh: Mesh,
                           feature_axis: str = "model") -> jax.Array:
    """Dual hat matrix from the feature-sharded Gram (λ > 0)."""
    g = distributed_gram(x, mesh, center=True, feature_axis=feature_axis)
    return fastcv.hat_matrix_dual(x, lam, gram=g)


def distributed_permutation_binary(
    x: jax.Array, y: jax.Array, folds: Folds, lam: float, n_perm: int,
    key: jax.Array, mesh: Mesh, *, metric: str = "accuracy",
    perm_axes: tuple = ("data",), feature_axis: str = "model",
    adjust_bias: bool = True,
):
    """Algorithm 1 at scale: Gram sharded over features, permutations over
    the DP axes. Returns PermutationResult-compatible (observed, null, p).
    """
    from repro.core import permutation as perm_lib

    h = distributed_hat_matrix(x, lam, mesh, feature_axis)
    plan = _plan_from_h(h, folds, adjust_bias)
    y = y.astype(h.dtype)

    dv_obs = fastcv.binary_dvals(plan, y, adjust_bias=adjust_bias)
    observed = perm_lib._fold_metric_binary(dv_obs, y[plan.te_idx], metric)

    y_perm = perm_lib.permutation_draws(key, y, n_perm)  # (T, N)
    null = mesh_null_program(mesh, metric=metric, perm_axes=perm_axes,
                             adjust_bias=adjust_bias)(plan, y_perm)
    return perm_lib.PermutationResult(observed, null,
                                      perm_lib.p_value(observed, null))


def mesh_null_body(mesh: Mesh, *, metric: str, perm_axes: tuple, adjust_bias: bool):
    """(plan, y_perm (B, N)) → (B,) replicated null: the draws padded (with
    the last draw) to whole shards over ``perm_axes``, evaluated by
    :func:`sharded_null_from_plan`, gathered (the all-gather is an op of
    the program that traces it) and cut back to B, in the default float
    dtype."""
    from repro.core import permutation as perm_lib

    def _mesh_null(plan, y_perm):
        b = y_perm.shape[0]
        t_pad = whole_shards(b, mesh, perm_axes)
        if t_pad > b:
            y_perm = jnp.pad(y_perm, ((0, t_pad - b), (0, 0)), mode="edge")
        null = sharded_null_from_plan(plan, y_perm, mesh, metric=metric,
                                      perm_axes=perm_axes, adjust_bias=adjust_bias)
        return perm_lib.as_default_float(replicated(null, mesh)[:b])

    return _mesh_null


class _OnMesh:
    """A jitted program called with its mesh ambient, which the gathers of
    a ``shard_map`` body need; ``_cache_size`` counts its compiled shapes."""

    def __init__(self, mesh: Mesh, program):
        self.mesh, self.program = mesh, program

    def __call__(self, *args):
        with _ambient(self.mesh):
            return self.program(*args)

    def _cache_size(self) -> int:
        return self.program._cache_size()


def mesh_null_program(mesh: Mesh, *, metric: str = "accuracy",
                      perm_axes: tuple = ("data",), adjust_bias: bool = True):
    """One jitted program (``jit__mesh_null``): (plan, y_perm (B, N)) → (B,)
    null metrics of the permuted label rows, replicated on every device of
    ``mesh``: :func:`mesh_null_body` (pad, sharded eval, all-gather,
    slice). A caller holds on to it so each shape compiles once.
    """
    body = mesh_null_body(mesh, metric=metric, perm_axes=perm_axes, adjust_bias=adjust_bias)
    return _OnMesh(mesh, jax.jit(body, out_shardings=NamedSharding(mesh, P())))


def mesh_permutation_program(mesh: Mesh, evaluate, *, null):
    """A whole permutation test on ``mesh`` as one program, also named
    ``jit__mesh_null``: :func:`repro.core.permutation.permutation_program`
    around the bodies it is handed, the observed labels evaluated
    replicated by ``evaluate`` and the draws by ``null`` (a body that
    shards them, such as :func:`mesh_null_body`). The labels, key and
    ``n_perm`` enter replicated through the program's input shardings; the
    packed test comes back replicated, so one transfer from any device
    reads it.
    """
    from repro.core import permutation as perm_lib

    rep = NamedSharding(mesh, P())
    program = perm_lib.permutation_program(
        evaluate, null=null, name="_mesh_null",
        in_shardings=(None, rep, rep, rep), out_shardings=rep)
    return _OnMesh(mesh, program)


def whole_shards(b: int, mesh: Mesh, perm_axes: tuple) -> int:
    """``b`` draws rounded up to a whole number of shards over ``perm_axes``."""
    n_shards = math.prod(mesh.shape[a] for a in perm_axes)
    return -(-b // n_shards) * n_shards


def sharded_null_from_plan(plan: fastcv.CVPlan, y_perm: jax.Array, mesh: Mesh, *,
                           metric: str = "accuracy",
                           perm_axes: tuple = ("data",),
                           adjust_bias: bool = True) -> jax.Array:
    """Null-distribution metrics for the permuted label rows ``y_perm``
    (T, N), T sharded over ``perm_axes`` (T a whole number of shards); the
    plan (hat matrix + fold factors) is replicated. Each shard runs
    :func:`repro.core.permutation.null_binary_metrics` on its rows. The
    (T,) result stays sharded over ``perm_axes``: gather it with
    :func:`replicated` before slicing it.

    This is the body of :func:`mesh_null_program` and
    :func:`mesh_permutation_program`, the serve engine's mesh null paths,
    which pad, gather and slice around it in one program.
    Called on its own, outside a jit, every op dispatches by itself. The
    plan enters as a replicated operand, not a closure: a closed-over
    array sharded on ``Explicit`` mesh axes (what ``jax.make_mesh`` gives)
    is rejected by ``shard_map``, and the body's gathers need the mesh set
    as the ambient one to place their indices.
    """
    from repro.core import permutation as perm_lib

    shard_fn = partial(perm_lib.null_binary_metrics, metric=metric,
                       adjust_bias=adjust_bias)
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(P(), P(perm_axes)),
                       out_specs=P(perm_axes))
    with _ambient(mesh):
        return fn(plan, y_perm)


def replicated(x: jax.Array, mesh: Mesh) -> jax.Array:
    """``x`` on every device of ``mesh``, unsharded: inside a jit, an
    all-gather of a sharded ``x``; outside, a transfer.

    A sharded null must be gathered before a prefix is sliced off it: on
    ``Explicit`` mesh axes a slice that cuts across shards has no
    unambiguous output sharding and is rejected.
    """
    return jax.device_put(x, NamedSharding(mesh, P()))


def _plan_from_h(h, folds: Folds, with_train_block: bool) -> fastcv.CVPlan:
    h_te = h[folds.te_idx[:, :, None], folds.te_idx[:, None, :]]
    eye = jnp.eye(h_te.shape[-1], dtype=h.dtype)
    from jax.scipy.linalg import cho_factor
    chol = jax.vmap(lambda a: cho_factor(a, lower=True)[0])(eye[None] - h_te)
    h_tr_te = (h[folds.tr_idx[:, :, None], folds.te_idx[:, None, :]]
               if with_train_block else None)
    return fastcv.CVPlan(h, folds.te_idx, folds.tr_idx, chol, h_tr_te)


def sharded_problems(fn, xs: jax.Array, mesh: Mesh, *shared,
                     problem_axes: tuple = ("pod", "data")) -> jax.Array:
    """Map ``fn(x, *shared)`` over the problem axis of ``xs`` (Q, ...), Q
    sharded over the mesh's problem axes (those present in the mesh are
    used). ``shared`` arrays (labels, fold indices) are replicated
    operands; ``fn`` must not close over device arrays, which ``shard_map``
    rejects when they are sharded on ``Explicit`` mesh axes.

    This is the generic problem-axis decomposition (paper §4.2:
    searchlights, time points, RSA sweeps): every problem is a fully
    independent CV computation, so the only collective is the final
    all-gather of the P(axes)-sharded output. ``fn`` takes one problem's
    leading-axis slice and may return any array (or pytree of arrays)
    whose leading output dimension is the problem dimension after vmap.
    """
    axes = tuple(a for a in problem_axes if a in mesh.axis_names)

    def shard_fn(xs_shard, *shared):
        return jax.vmap(lambda x: fn(x, *shared))(xs_shard)

    in_specs = (P(axes),) + (P(),) * len(shared)
    mapped = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                           out_specs=P(axes))
    with _ambient(mesh):
        return mapped(xs, *shared)


def searchlight_cv(xs: jax.Array, y: jax.Array, folds: Folds, lam: float,
                   mesh: Mesh, *, problem_axes: tuple = ("pod", "data"),
                   adjust_bias: bool = True):
    """Many independent CV problems (paper §4.2: searchlight / time points /
    RSA pairs): xs (Q, N, P_local_features) sharded over the problem axes.

    Each problem runs the full analytical CV locally — zero cross-problem
    communication. Returns per-problem accuracy (Q,).
    """
    def one_problem(x, y, te_idx, tr_idx):
        dv, y_te = fastcv.binary_cv(x, y, Folds.with_indices(te_idx, tr_idx),
                                    lam=lam, adjust_bias=adjust_bias)
        pred = jnp.where(dv >= 0, 1.0, -1.0)
        return jnp.mean(pred == jnp.sign(y_te))

    return sharded_problems(one_problem, xs, mesh, y, folds.te_idx,
                            folds.tr_idx, problem_axes=problem_axes)
