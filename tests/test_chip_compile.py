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
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import fastcv, multiclass
from repro.kernels.fold_eval import ops as fold_eval_ops
from repro.kernels.foldsolve import ops as foldsolve_ops
from repro.kernels.gram.ops import gram
from repro.kernels.pairdist.ops import pairwise_sq_dists

N, P = 787, 1900  # trials × windowed features (paper §2.13)
K, M = 10, 79  # folds × test trials per fold
F32 = jnp.float32


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described TPU v5e 2×2 host."""
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
            yield topo.devices
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


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


def test_multiclass_null_eval_has_no_eigh_for_v5e(one_chip):
    """The 3-class null eval the engine dispatches (1024 draws × K folds of
    3 × 3 step-2 problems) solves them by elementwise Jacobi sweeps: the
    compiled program holds no ``eigh`` custom call."""
    from repro.serve import CVEngine

    fn = CVEngine()._perm_multiclass_fn(3)
    text = _compile(fn, _plan_spec(one_chip, True), _spec((1024, N), jnp.int32, one_chip))
    assert "Eigh" not in text


def _scalar_gathers(text: str) -> list:
    """The compiled program's gathers that read one element per index."""
    return [line for line in text.splitlines()
            if " gather(" in line and "slice_sizes={1}" in line]


@pytest.mark.parametrize("estimator", ["binary", "multiclass"])
def test_null_eval_gathers_no_scalars_for_v5e(one_chip, estimator):
    """The engine's null programs (``jit__eval``) read 1024 draws of
    permuted labels as rows: no gather of one label at a time is left."""
    from repro.serve import CVEngine

    if estimator == "binary":
        fn, y = CVEngine()._perm_binary_fn("accuracy", True), _spec((1024, N), F32, one_chip)
    else:
        fn, y = CVEngine()._perm_multiclass_fn(3), _spec((1024, N), jnp.int32, one_chip)
    text = _compile(fn, _plan_spec(one_chip, True), y)
    assert " gather(" in text  # the row gathers of the fold split remain
    assert _scalar_gathers(text) == []


def test_mesh_null_gathers_no_scalars_for_v5e(v5e_2x2):
    """The mesh null (``jit__mesh_null``) at 10,240 draws over a 2 × 2 mesh,
    as the four-chip cell runs it: each chip reads its rows of permuted
    labels, with no gather of one label at a time."""
    from repro.core.distributed import mesh_null_program

    mesh = Mesh(np.asarray(v5e_2x2).reshape(2, 2), ("data", "model"))
    everywhere = NamedSharding(mesh, PartitionSpec())
    fn = mesh_null_program(mesh, perm_axes=("data", "model"))
    with jax.set_mesh(mesh):
        text = _compile(fn, _plan_spec(everywhere, True), _spec((10240, N), F32, everywhere))
    assert "all-gather" in text
    assert _scalar_gathers(text) == []
