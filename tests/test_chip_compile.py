"""Compile the served path for a described TPU v5e chip, at deployment widths.

Nothing runs here: each test lowers and compiles one kernel or jitted eval
for a TPU v5e that is described, not attached, so the TPU compiler refuses
here what it would refuse on the chip (float64, misaligned blocks, too
much fast memory). Widths are the source paper's EEG analysis (§2.13):
787 trials × 1900 windowed features, K = 10 folds of m = 79, float32.

The topology is described inside a module fixture, never at import, and
the compilation cache is off around the compiles (a compile for a
described chip cannot be read back without one). The compiles trace with
x64 off, as the chip path runs; under the suite's x64 the kernels trace
64-bit values, which the TPU compiler rejects.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import fastcv, multiclass
from repro.kernels.fold_eval import ops as fold_eval_ops
from repro.kernels.foldsolve import ops as foldsolve_ops
from repro.kernels.gram.ops import gram
from repro.kernels.pairdist.ops import pairwise_sq_dists

N, P = 787, 1900  # trials × windowed features (paper §2.13)
K, M = 10, 79  # folds × test trials per fold
F32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was_on)
            compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The fused evals resolve ``interpret`` from the backend, which is the
    CPU here: steer them onto the compiled kernels for these tests only."""
    monkeypatch.setattr(fold_eval_ops, "default_interpret", lambda: False)
    monkeypatch.setattr(foldsolve_ops, "default_interpret", lambda: False)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """HLO text of ``fn`` compiled for the described chip, traced with x64
    off like the chip path."""
    with jax.enable_x64(False):
        return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("precision", ["fp32", "bf16_gram"])
def test_gram_compiles_for_v5e(one_chip, precision):
    fn = functools.partial(gram, center=True, precision=precision, interpret=False)
    text = _compile(fn, _spec((N, P), F32, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b", [1, 128])
def test_fold_eval_compiles_for_v5e(one_chip, b):
    fn = functools.partial(fold_eval_ops.fold_eval, interpret=False)
    text = _compile(
        fn,
        _spec((K, M, N), F32, one_chip),
        _spec((K, M, M), F32, one_chip),
        _spec((N, b), F32, one_chip),
        _spec((K, M, b), F32, one_chip),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b", [1, 128])
def test_foldsolve_compiles_for_v5e(one_chip, b):
    fn = functools.partial(foldsolve_ops.foldsolve, interpret=False)
    text = _compile(fn, _spec((K, M, M), F32, one_chip), _spec((K, M, b), F32, one_chip))
    assert "tpu_custom_call" in text


def test_pairdist_compiles_for_v5e(one_chip):
    fn = functools.partial(pairwise_sq_dists, interpret=False)
    text = _compile(fn, _spec((3, P), F32, one_chip))
    assert "tpu_custom_call" in text


def _plan_spec(sharding, with_train_block):
    return fastcv.CVPlan(
        h=_spec((N, N), F32, sharding),
        te_idx=_spec((K, M), jnp.int32, sharding),
        tr_idx=_spec((K, N - M), jnp.int32, sharding),
        chol_ih=_spec((K, M, M), F32, sharding),
        h_tr_te=_spec((K, N - M, M), F32, sharding) if with_train_block else None,
    )


@pytest.mark.parametrize("estimator", ["binary", "ridge", "multiclass"])
def test_fused_eval_compiles_for_v5e(one_chip, compiled_kernels, estimator):
    if estimator == "binary":
        fn = fastcv.make_eval_binary(adjust_bias=True, fused=True)
        plan, y = _plan_spec(one_chip, True), _spec((N, 1), F32, one_chip)
    elif estimator == "ridge":
        fn = fastcv.make_eval_cv(fused=True)
        plan, y = _plan_spec(one_chip, False), _spec((N, 1), F32, one_chip)
    else:
        fn = multiclass.make_eval_multiclass(3, fused=True)
        plan, y = _plan_spec(one_chip, True), _spec((1, N), jnp.int32, one_chip)
    assert "tpu_custom_call" in _compile(fn, plan, y)
