"""Every float32 contraction on the served path asks for full float32.

On a TPU a float32 matmul at the default precision is one bfloat16 pass,
which misses the chip smoke's 1e-3 agreement with a float64 refit. The
precision is set per contraction (``repro.kernels.common.dot_precision``),
so each family the engine serves is traced here in float32, as the chip
runs it, and every ``dot_general`` with a float32 operand — in XLA code and
inside the Pallas kernels — must carry ``Precision.HIGHEST``. A new
contraction that forgets it fails here, not on the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jcore

from repro.core import distributed, fastcv, multiclass, multidim, tuning
from repro.core import folds as foldlib
from repro.kernels.gram.ops import centered_gram, gram
from repro.kernels.hat_apply.ops import hat_errors
from repro.kernels.pairdist.ops import pairwise_sq_dists
from repro.rsa import rdm as rsa_rdm

N, P, K = 24, 40, 4
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _dots(jaxpr):
    """Every dot_general equation in ``jaxpr`` and its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _dots(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _dots(sub)


def _f32_dots(fn, *args):
    with jax.enable_x64(False):
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return [e for e in _dots(jaxpr) if any(v.aval.dtype == F32 for v in e.invars)]


def _plan(with_train_block=True):
    x = jax.ShapeDtypeStruct((N, P), F32)
    folds = foldlib.kfold(N, K, seed=0)
    with jax.enable_x64(False):
        return jax.eval_shape(
            lambda x: fastcv.prepare(x, folds, 1.0, with_train_block=with_train_block), x
        )


def _families():
    x = jax.ShapeDtypeStruct((N, P), F32)
    y = jax.ShapeDtypeStruct((N, 3), F32)
    folds = foldlib.kfold(N, K, seed=0)
    plan, plan_no_tr = _plan(), _plan(with_train_block=False)
    labels = jax.ShapeDtypeStruct((2, N), jnp.int32)
    yield "plan/dual", lambda x: fastcv.prepare(x, folds, 1.0, mode="dual"), (x,)
    yield "plan/primal", lambda x: fastcv.prepare(x[:, :8], folds, 1.0, mode="primal"), (x,)
    yield "gram/pallas", lambda x: centered_gram(x, interpret=True), (x,)
    yield "gram/pallas-bf16", lambda x: gram(x, precision="bf16_gram", interpret=True), (x,)
    for fused in (False, True):
        tag = "fused" if fused else "xla"
        yield f"eval/binary/{tag}", fastcv.make_eval_binary(True, fused=fused), (plan, y)
        yield (f"eval/binary-no-bias/{tag}", fastcv.make_eval_binary(False, fused=fused),
               (plan_no_tr, y))
        yield f"eval/ridge/{tag}", fastcv.make_eval_cv(fused=fused), (plan_no_tr, y)
        yield (f"eval/multiclass/{tag}", multiclass.make_eval_multiclass(3, fused=fused),
               (plan, labels))
        for diss in ("accuracy", "contrast"):
            yield (f"eval/rsa-{diss}/{tag}", rsa_rdm.make_eval_pairs(diss, True, fused=fused),
                   (plan, y))
    yield "pairdist", lambda u: pairwise_sq_dists(u, interpret=True), (
        jax.ShapeDtypeStruct((3, P), F32),)
    yield "hat_apply", lambda h, y: hat_errors(h, y, interpret=True), (
        jax.ShapeDtypeStruct((N, N), F32), y)
    yield "tune", lambda x, y: tuning.tune_ridge(x, y), (x, jax.ShapeDtypeStruct((N,), F32))
    yield "grid", lambda xs, y: multidim.cv_grid(xs, y, folds, 1.0), (
        jax.ShapeDtypeStruct((2, N, P), F32), jax.ShapeDtypeStruct((N,), F32))


FAMILIES = {name: (fn, args) for name, fn, args in _families()}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_float32_contractions_ask_for_highest(family):
    fn, args = FAMILIES[family]
    dots = _f32_dots(fn, *args)
    if family == "gram/pallas-bf16":
        # bf16_gram: the one contraction is bf16 × bf16 → f32 by design
        assert dots == [] or all(
            v.aval.dtype == jnp.bfloat16 for e in dots for v in e.invars
        )
        return
    assert dots, f"{family}: no float32 contraction traced"
    low = [e for e in dots if tuple(e.params["precision"] or ()) != (HIGHEST, HIGHEST)]
    assert not low, f"{family}: {len(low)} of {len(dots)} contractions below HIGHEST: " + "; ".join(
        f"{[v.aval for v in e.invars]} precision={e.params['precision']}" for e in low
    )


def test_distributed_gram_asks_for_highest():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    x = jax.ShapeDtypeStruct((N, P), F32)
    with jax.set_mesh(mesh):
        dots = _f32_dots(lambda x: distributed.distributed_gram(x, mesh), x)
    assert dots
    assert all(tuple(e.params["precision"]) == (HIGHEST, HIGHEST) for e in dots)
