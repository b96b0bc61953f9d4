"""Permutation testing with the analytical approach (paper §2.7, Alg. 1 & 2).

The hat matrix H depends on features only, so it is computed ONCE; each
permutation σ only needs ŷ = H yσ and the per-fold solves against the
*pre-factorised* (I − H_Te). Beyond the paper: the Cholesky factors are
shared across permutations (O(m³) → O(m²) per permutation per fold) and
permutations are processed in static-size chunks via ``lax.map`` so T can
be large without exhausting memory; chunks are the unit the distributed
engine shards over the ("pod", "data") mesh axes. A draw is the permuted
label row itself (:func:`permutation_draws`), so a null reads its labels
directly instead of gathering them through an index row.

The serve engine composes the draws with the rest of a test in
:func:`permutation_program`: draws, observed evaluation, null and p-value
in one jitted program per shape bucket, read back with one fetch
(:func:`unpack_test`). Its streamed null draws with
:func:`permutation_draws` directly, chunk by chunk.

Standard-approach baselines (retrain K models per permutation) are provided
for the benchmark comparison (Fig. 3 right panels, Fig. 4).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fastcv, lda, metrics, multiclass
from repro.core.folds import Folds

__all__ = [
    "PermutationResult",
    "permutation_draws",
    "permutation_indices",
    "null_binary_metrics",
    "analytical_permutation_binary",
    "standard_permutation_binary",
    "analytical_permutation_multiclass",
    "standard_permutation_multiclass",
    "p_value",
    "prng_key",
    "permutation_test",
    "permutation_program",
    "unpack_test",
    "as_default_float",
]


class PermutationResult(NamedTuple):
    """Observed metric, null and p-value. The library's functions return
    device arrays; the serve engine returns host NumPy values, cut from the
    one array its permutation program hands back (:func:`unpack_test`)."""

    observed: jax.Array    # () metric on unpermuted labels
    null: jax.Array        # (T,) null distribution
    p: jax.Array           # () permutation p-value


def p_value(observed: jax.Array, null: jax.Array, n_perm=None) -> jax.Array:
    """(1 + #{null >= obs}) / (1 + T) — standard permutation p-value.

    With ``n_perm`` (a traced scalar is fine) only the first ``n_perm``
    rows of ``null`` count, and T is ``n_perm``: a null evaluated at a
    padded bucket size gives the p-value of its requested prefix."""
    if n_perm is None:
        return (1.0 + jnp.sum(null >= observed)) / (1.0 + null.shape[0])
    counted = jnp.arange(null.shape[0]) < n_perm
    return (1.0 + jnp.sum((null >= observed) & counted)) / (1.0 + n_perm)


def prng_key(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)``'s raw key (uint32 (2,)), made on the host.

    Bit for bit the key JAX makes from a Python integer, under either
    setting of ``jax_enable_x64``: the seed's two 32-bit halves, the high
    one zero unless x64 is on. It enters a program as an argument, so a
    request's seed costs no device dispatch of its own.
    """
    if jax.config.jax_default_prng_impl != "threefry2x32":
        raise ValueError("prng_key makes threefry2x32 keys only")
    s = int(np.int64(seed)) + int(jax.config.jax_random_seed_offset)
    hi = (s >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([hi, s & 0xFFFFFFFF], np.uint32)


@partial(jax.jit, static_argnames=("n_perm",))
def permutation_draws(key: jax.Array, payload: jax.Array, n_perm: int) -> jax.Array:
    """(T, len(payload)) rows: draw t is ``payload`` shuffled under the key
    ``fold_in(key, t)``.

    Jitted (the serve engine draws per request, so dispatch overhead
    matters) and *prefix-stable*: draw t depends only on (key, t), so
    requesting a larger T — e.g. the engine rounding T up to a shape
    bucket — yields the same leading rows as a direct call. That keeps
    engine null distributions identical to the library's for any shared
    key. The shuffle is a stable sort of ``payload`` by random keys, so its
    moves depend on the keys alone: ``permutation_draws(key, y, T)`` equals
    ``y[permutation_indices(key, len(y), T)]`` exactly, and a null reads
    its permuted labels without gathering them one element at a time.
    """
    keys = jax.vmap(lambda t: jax.random.fold_in(key, t))(jnp.arange(n_perm))
    return jax.vmap(lambda k: jax.random.permutation(k, payload))(keys)


@partial(jax.jit, static_argnames=("n", "n_perm"))
def permutation_indices(key: jax.Array, n: int, n_perm: int) -> jax.Array:
    """(T, N) index rows: :func:`permutation_draws` of ``arange(n)``.

    For nulls that permute something other than a label row (RSA permutes
    an RDM's rows and columns) and for references that index labels."""
    return permutation_draws(key, jnp.arange(n), n_perm)


def _fold_metric_binary(dvals, y_te, metric: str):
    """Per-fold metric averaged over folds. dvals/y_te: (K, m[, B])."""
    if metric == "accuracy":
        pred = jnp.where(dvals >= 0, 1.0, -1.0)
        return jnp.mean(pred == jnp.sign(y_te), axis=(0, 1))
    if metric == "auc":
        if dvals.ndim == 2:
            return jnp.mean(jax.vmap(metrics.auc)(dvals, y_te))
        per_fold = jax.vmap(jax.vmap(metrics.auc, in_axes=-1), in_axes=0)
        return jnp.mean(per_fold(dvals, y_te), axis=0)
    raise ValueError(f"unknown metric {metric!r}")


def null_binary_metrics(plan: fastcv.CVPlan, y_perm: jax.Array, *,
                        metric: str = "accuracy", adjust_bias: bool = True) -> jax.Array:
    """(B,) binary metrics of the permuted label rows ``y_perm`` (B, N).

    The one null evaluation: the serve engine's local program, the mesh
    program's per-shard body and the library's chunks all call it."""
    yp = y_perm.T                                             # (N, B)
    dv = fastcv.binary_dvals(plan, yp, adjust_bias=adjust_bias)
    return _fold_metric_binary(dv, yp[plan.te_idx], metric)


def permutation_test(plan, y: jax.Array, key: jax.Array, n_perm, *, t_gen: int,
                     evaluate, null=None) -> jax.Array:
    """One permutation test, traced into the caller's program.

    Draws ``t_gen`` permuted label rows of ``y`` (:func:`permutation_draws`),
    evaluates ``y`` itself as one row with ``evaluate(plan, rows) -> (B,)``
    and the draws with ``null`` (``evaluate`` when None), and counts the
    first ``n_perm`` draws into the p-value. Returns
    ``[observed, p, null (t_gen,)]`` packed in one array of JAX's default
    float dtype, so the caller reads the whole test back with one transfer
    (:func:`unpack_test`).
    """
    null = evaluate if null is None else null
    draws = permutation_draws(key, y, t_gen)
    observed = evaluate(plan, y[None])[0]
    dist = null(plan, draws)
    p = p_value(observed, dist, n_perm)
    return jnp.concatenate([as_default_float(a) for a in (observed[None], p[None], dist)])


def as_default_float(a: jax.Array) -> jax.Array:
    """``a`` in JAX's default float dtype (float32, float64 under x64): the
    one dtype of the engine's permutation metrics, so a packed test keeps
    its p-value's precision and a streamed null the one-shot null's dtype."""
    return a.astype(jnp.result_type(float))


def permutation_program(evaluate, *, null=None, name: str = "_eval", **jit_options):
    """jit[(plan, y, key, n_perm, t_gen) -> packed test] of
    :func:`permutation_test` around the bodies it is handed; all arguments
    positional, ``t_gen`` static.

    ``n_perm`` is traced, so every request within one bucket ``t_gen``
    runs one compiled program. ``name`` names the XLA module
    (``jit_<name>``); ``jit_options`` go to :func:`jax.jit` (the mesh path
    passes its shardings).
    """
    def program(plan, y, key, n_perm, t_gen):
        return permutation_test(plan, y, key, n_perm, t_gen=t_gen, evaluate=evaluate,
                                null=null)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program, static_argnums=4, **jit_options)


def unpack_test(packed: np.ndarray, n_perm: int) -> PermutationResult:
    """The host-side :class:`PermutationResult` of a fetched
    :func:`permutation_test`: scalars and the null's first ``n_perm`` rows."""
    return PermutationResult(packed[0], packed[2:2 + n_perm], packed[1])


def analytical_permutation_binary(
    x: jax.Array, y: jax.Array, folds: Folds, lam: float, n_perm: int,
    key: jax.Array, metric: str = "accuracy", mode: str = "auto",
    chunk: int = 256, adjust_bias: bool = True,
) -> PermutationResult:
    """Algorithm 1: H once, then T permutations of cheap fold-solves."""
    plan = fastcv.prepare(x, folds, lam, mode=mode, with_train_block=adjust_bias)
    y = y.astype(plan.h.dtype)

    dv_obs = fastcv.binary_dvals(plan, y, adjust_bias=adjust_bias)
    observed = _fold_metric_binary(dv_obs, y[plan.te_idx], metric)

    y_perm = permutation_draws(key, y, n_perm)               # (T, N)
    chunk = min(chunk, n_perm)
    n_chunks = -(-n_perm // chunk)
    pad = n_chunks * chunk - n_perm
    y_perm = jnp.pad(y_perm, ((0, pad), (0, 0)), mode="edge")
    y_perm = y_perm.reshape(n_chunks, chunk, -1)

    def one_chunk(rows):
        return null_binary_metrics(plan, rows, metric=metric, adjust_bias=adjust_bias)

    null = jax.lax.map(one_chunk, y_perm).reshape(-1)[:n_perm]
    return PermutationResult(observed, null, p_value(observed, null))


def standard_permutation_binary(
    x: jax.Array, y: jax.Array, folds: Folds, lam: float, n_perm: int,
    key: jax.Array, metric: str = "accuracy",
) -> PermutationResult:
    """Paper's standard approach: retrain K classifiers per permutation."""
    y = y.astype(x.dtype)
    dv_obs, y_te = lda.standard_cv_binary(x, y, folds, lam=lam)
    observed = _fold_metric_binary(dv_obs, y_te, metric)

    @jax.jit
    def one_perm(yp):
        dv, yte = lda._standard_cv_binary_jit(
            x, yp, folds.te_idx, folds.tr_idx, jnp.asarray(lam, x.dtype), "lda")
        return _fold_metric_binary(dv, yte, metric)

    null = jax.lax.map(one_perm, permutation_draws(key, y, n_perm))
    return PermutationResult(observed, null, p_value(observed, null))


def analytical_permutation_multiclass(
    x: jax.Array, y: jax.Array, folds: Folds, num_classes: int, lam: float,
    n_perm: int, key: jax.Array, mode: str = "auto", chunk: int = 64,
) -> PermutationResult:
    """Algorithm 2 under permutations: step 1 batched through the shared
    plan; step 2 (C×C eigh) vmapped over (folds × permutations)."""
    plan = fastcv.prepare(x, folds, lam, mode=mode, with_train_block=True)
    dtype = plan.h.dtype

    pred_obs, y_te_obs = multiclass.analytical_cv_multiclass(
        x, y, folds, num_classes, lam, mode=mode, plan=plan)
    observed = metrics.multiclass_accuracy(pred_obs, y_te_obs)

    y_perm = permutation_draws(key, y, n_perm)
    chunk = min(chunk, n_perm)
    n_chunks = -(-n_perm // chunk)
    pad = n_chunks * chunk - n_perm
    y_perm = jnp.pad(y_perm, ((0, pad), (0, 0)), mode="edge")
    y_perm = y_perm.reshape(n_chunks, chunk, -1)

    def one_perm(yp):
        y1h = multiclass.onehot(yp, num_classes, dtype=dtype)
        y_dot_te, y_dot_tr = fastcv.cv_errors(plan, y1h)
        y1h_tr = y1h[plan.tr_idx]
        preds = jax.vmap(multiclass._fold_predict, in_axes=(0, 0, 0, None))(
            y_dot_te, y_dot_tr, y1h_tr, dtype)
        return metrics.multiclass_accuracy(preds, yp[plan.te_idx])

    null = jax.lax.map(jax.vmap(one_perm), y_perm).reshape(-1)[:n_perm]
    return PermutationResult(observed, null, p_value(observed, null))


def standard_permutation_multiclass(
    x: jax.Array, y: jax.Array, folds: Folds, num_classes: int, lam: float,
    n_perm: int, key: jax.Array,
) -> PermutationResult:
    """Standard approach: retrain direct multi-class LDA K times per σ."""
    pred_obs, y_te_obs = multiclass.standard_cv_multiclass(
        x, y, folds, num_classes, lam)
    observed = metrics.multiclass_accuracy(pred_obs, y_te_obs)

    @jax.jit
    def one_perm(yp):
        pred, yte = multiclass._standard_cv_multiclass_jit(
            x, yp, folds.te_idx, folds.tr_idx, jnp.asarray(lam, x.dtype),
            num_classes)
        return metrics.multiclass_accuracy(pred, yte)

    null = jax.lax.map(one_perm, permutation_draws(key, y, n_perm))
    return PermutationResult(observed, null, p_value(observed, null))
