"""Analytical k-fold cross-validation for least-squares models.

This module is the paper's primary contribution (Treder 2018, §2.4-2.6):
exact cross-validated decision values for any ridge-regularised
least-squares model (linear regression, ridge regression, binary LDA in
regression form) from a *single* full-data fit.

    H  = X̃ (X̃ᵀX̃ + λI₀)⁻¹ X̃ᵀ          (hat matrix, Eq. 8 + §2.6.1)
    ŷ  = H y,   ê = y − ŷ
    ė_Te = (I − H_Te)⁻¹ ê_Te            (Eq. 14 — the analytical approach)
    ẏ_Te = y_Te − ė_Te
    ė_Tr = ê_Tr + H_{Tr,Te} (I − H_Te)⁻¹ ê_Te        (Eq. 15, bias adjust)

TPU-adapted design decisions (DESIGN.md §2):

* Two hat-matrix paths, selected by shape:
    - *primal* (N > P): the paper's explicit augmented form with the
      unpenalised-intercept matrix I₀.
    - *dual* (P ≫ N, the paper's own target regime): column-center X, then
      ``H = 1/N·11ᵀ + G_c (G_c + λI)⁻¹`` with ``G_c = X_c X_cᵀ``. This is
      algebraically identical to the primal form (push-through identity +
      unpenalised intercept ≡ centering) but only ever materialises N×N
      objects; the O(N²P) Gram product is the MXU-friendly hot-spot served
      by the Pallas ``gram`` kernel.
* Folds are static-shape index arrays; all K fold-solves are one batched
  Cholesky (``vmap(cho_factor)``), and the factorisation is *reused across
  permutations* — a beyond-paper optimisation (the paper re-solves per
  permutation; we factor once per fold: O(m³) → O(m²) per permutation).
"""

from __future__ import annotations

import dataclasses
import hashlib
import weakref
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.linalg import cho_factor, cho_solve

from repro.core.folds import Folds
from repro.kernels.common import dot_precision

# reprolint: host-float64
# (The incremental update lineage — update_plan/downdate_plan/
# sliding_window and their helpers — is bit-exact against from-scratch
# rebuilds only because every host correction stays IEEE float64, per
# arXiv 2401.13185. RL005 flags any sub-float64 dtype in this module.)

__all__ = [
    "hat_matrix",
    "hat_matrix_primal",
    "hat_matrix_dual",
    "CVPlan",
    "prepare",
    "cv_errors",
    "cv_errors_fused",
    "binary_dvals",
    "binary_cv",
    "fingerprint",
    "plan_key",
    "plan_to_arrays",
    "plan_from_arrays",
    "make_eval_binary",
    "make_eval_cv",
    "update_plan",
    "downdate_plan",
    "sliding_window",
]


def _augment(x: jax.Array) -> jax.Array:
    """X̃ = [X, 1] — append the intercept column (paper §2.3)."""
    n = x.shape[0]
    return jnp.concatenate([x, jnp.ones((n, 1), x.dtype)], axis=1)


def hat_matrix_primal(x: jax.Array, lam: float = 0.0) -> jax.Array:
    """H = X̃ (X̃ᵀX̃ + λI₀)⁻¹ X̃ᵀ — the paper's explicit form.

    O(NP² + P³). Requires X̃ᵀX̃ + λI₀ to be positive definite (N > P or
    λ > 0 with a full-rank intercept-augmented design).
    """
    xa = _augment(x)
    p1 = xa.shape[1]
    # I₀: identity with the intercept entry zeroed (bias never penalised).
    i0 = jnp.eye(p1, dtype=x.dtype).at[p1 - 1, p1 - 1].set(0.0)
    hi = dot_precision(x.dtype)
    a = jnp.matmul(xa.T, xa, precision=hi) + jnp.asarray(lam, x.dtype) * i0
    c = cho_factor(a)
    return jnp.matmul(xa, cho_solve(c, xa.T), precision=hi)


def hat_matrix_dual(x: jax.Array, lam: float, gram: Optional[jax.Array] = None) -> jax.Array:
    """H = 1/N·11ᵀ + G_c (G_c + λI)⁻¹, G_c = X_c X_cᵀ — dual / kernel form.

    O(N²P + N³); never materialises a P×P matrix. Exact for λ > 0 (the
    paper's recommended operating point in high dimensions). ``gram`` may
    be supplied precomputed (e.g. by the Pallas kernel or the distributed
    feature-sharded reduction).
    """
    n = x.shape[0]
    if gram is None:
        xc = x - jnp.mean(x, axis=0, keepdims=True)
        gram = jnp.matmul(xc, xc.T, precision=dot_precision(x.dtype))
    lam = jnp.asarray(lam, x.dtype)
    c = cho_factor(gram + lam * jnp.eye(n, dtype=x.dtype))
    # G (G+λI)⁻¹ is symmetric (G and (G+λI)⁻¹ share an eigenbasis).
    h_c = cho_solve(c, gram)
    h_c = 0.5 * (h_c + h_c.T)
    return h_c + jnp.full((n, n), 1.0 / n, x.dtype)


def hat_matrix(x: jax.Array, lam: float = 0.0, mode: str = "auto",
               gram: Optional[jax.Array] = None) -> jax.Array:
    """Dispatch between primal and dual hat-matrix construction.

    mode="auto" picks dual when P >= N (the paper's P ≫ N regime), primal
    otherwise. λ = 0 in the P >= N regime is rejected: the unregularised
    interpolator has H_Te → I and Eq. (14) becomes singular (the paper
    implicitly assumes ridge regularisation there).
    """
    n, p = x.shape
    if mode == "auto":
        mode = "dual" if p >= n else "primal"
    if mode == "dual":
        # Only checkable when lam is a concrete Python number (outside jit).
        if isinstance(lam, (int, float)) and lam <= 0.0:
            raise ValueError("dual hat matrix requires lam > 0 (P >= N regime)")
        return hat_matrix_dual(x, lam, gram=gram)
    if mode == "primal":
        return hat_matrix_primal(x, lam)
    raise ValueError(f"unknown mode {mode!r}")


# ---------------------------------------------------------------------------
# CV plan: everything that depends on (X, folds, λ) but not on labels.
# Reused across permutations (§2.7: H is label-invariant).
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CVPlan:
    """Precomputed label-independent quantities for analytical CV.

    Attributes:
      h: (N, N) hat matrix.
      te_idx: (K, m) test indices.  tr_idx: (K, N-m) train indices.
      chol_ih: (K, m, m) Cholesky factors (lower) of I − H_Te per fold.
      h_tr_te: (K, N-m, m) cross blocks H_{Tr,Te} (None unless bias adjust).
    """

    h: jax.Array
    te_idx: jax.Array
    tr_idx: jax.Array
    chol_ih: jax.Array
    h_tr_te: Optional[jax.Array]

    def tree_flatten(self):
        return (self.h, self.te_idx, self.tr_idx, self.chol_ih, self.h_tr_te), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def k(self) -> int:
        return self.te_idx.shape[0]

    @property
    def nbytes(self) -> int:
        """Device bytes held by the plan — the plan-cache accounting unit."""
        leaves = [self.h, self.te_idx, self.tr_idx, self.chol_ih]
        if self.h_tr_te is not None:
            leaves.append(self.h_tr_te)
        return int(sum(a.size * a.dtype.itemsize for a in leaves))


@partial(jax.jit, static_argnames=("mode", "with_train_block", "lam"))
def _prepare_jit(x, te_idx, tr_idx, lam, mode, with_train_block, gram=None):
    h = hat_matrix(x, lam, mode=mode, gram=gram)
    h_te = h[te_idx[:, :, None], te_idx[:, None, :]]           # (K, m, m)
    eye = jnp.eye(h_te.shape[-1], dtype=h.dtype)
    ih = eye[None] - h_te
    chol = jax.vmap(lambda a: cho_factor(a, lower=True)[0])(ih)
    h_tr_te = (
        h[tr_idx[:, :, None], te_idx[:, None, :]] if with_train_block else None
    )
    return h, chol, h_tr_te


def prepare(x: jax.Array, folds: Folds, lam: float = 0.0, mode: str = "auto",
            with_train_block: bool = True,
            gram: Optional[jax.Array] = None,
            precision: Optional[str] = None) -> CVPlan:
    """Build a :class:`CVPlan`: hat matrix + per-fold factorisations.

    This is the one-time O(N²P + N³ + K·m³) setup; every subsequent label
    vector (CV run or permutation) costs only O(K·m²) per evaluation.

    ``gram`` may carry a precomputed *centered* Gram G_c = X_c X_cᵀ (dual
    mode only) — the serve engine feeds the Pallas ``gram`` kernel's or the
    feature-sharded ``distributed_gram``'s output here, keeping the O(N²P)
    hot path off the XLA default lowering.

    ``precision="bf16_gram"`` (dual mode only) builds the Gram product —
    the only O(N²P) contraction — from a bf16 cast of the centered design
    with f32 accumulation, while every solve stays full precision (see
    :mod:`repro.kernels.gram.ops` for the error bound). Primal-mode builds
    have no Gram and are always full precision; requesting the mode there
    is an error rather than a silent no-op. A caller-supplied ``gram`` is
    trusted to already honour the requested precision (the engine computes
    it through the same helpers).
    """
    n, p = x.shape
    if mode == "auto":
        mode = "dual" if p >= n else "primal"
    if mode == "dual" and lam <= 0.0:
        raise ValueError("analytical CV with P >= N requires lam > 0 "
                         "(unregularised interpolation makes I - H_Te singular)")
    if gram is not None and mode != "dual":
        raise ValueError("precomputed gram only applies to dual mode")
    from repro.kernels.gram.ops import centered_gram_xla, check_precision
    precision = check_precision(precision)
    if precision != "fp32":
        if mode != "dual":
            raise ValueError(
                f"precision={precision!r} only applies to dual-mode plans "
                "(the primal build has no Gram product to down-cast)")
        if gram is None:
            gram = centered_gram_xla(x, precision=precision)
    h, chol, h_tr_te = _prepare_jit(
        x, folds.te_idx, folds.tr_idx, float(lam), mode, with_train_block,
        gram
    )
    return CVPlan(h, folds.te_idx, folds.tr_idx, chol, h_tr_te)


def _chol_solve_lower(chol_l: jax.Array, b: jax.Array) -> jax.Array:
    return cho_solve((chol_l, True), b)


def cv_errors(plan: CVPlan, y: jax.Array, *, fused: bool = False):
    """Eq. (14) + Eq. (15) for a label/response matrix ``y`` of shape (N, ...).

    Returns (y_dot_te, y_dot_tr):
      y_dot_te: (K, m, ...)    exact CV predictions on each test fold.
      y_dot_tr: (K, N-m, ...)  exact *training-set* predictions of each
                               fold model (None if plan lacks train blocks).

    ``y`` may carry trailing batch dims (e.g. permutations, classes); the
    fold solves broadcast over them using the cached Cholesky factors.

    ``fused=True`` routes through the Pallas kernels
    (:func:`cv_errors_fused`) — same results within kernel parity
    tolerances; worthwhile on TPU, interpret-mode slow elsewhere.
    """
    if fused:
        return cv_errors_fused(plan, y)
    squeeze = y.ndim == 1
    if squeeze:
        y = y[:, None]
    hi = dot_precision(plan.h.dtype)
    y_hat = jnp.matmul(plan.h, y, precision=hi)  # (N, B)
    e_hat = y - y_hat
    e_te = e_hat[plan.te_idx]                   # (K, m, B)
    t = jax.vmap(_chol_solve_lower)(plan.chol_ih, e_te)   # (I−H_Te)⁻¹ ê_Te
    y_dot_te = y[plan.te_idx] - t               # ẏ_Te = y_Te − ė_Te
    y_dot_tr = None
    if plan.h_tr_te is not None:
        e_tr = e_hat[plan.tr_idx]               # (K, N-m, B)
        e_dot_tr = e_tr + jnp.einsum("knm,kmb->knb", plan.h_tr_te, t, precision=hi)
        y_dot_tr = y[plan.tr_idx] - e_dot_tr
    if squeeze:
        y_dot_te = y_dot_te[..., 0]
        y_dot_tr = None if y_dot_tr is None else y_dot_tr[..., 0]
    return y_dot_te, y_dot_tr


def cv_errors_fused(plan: CVPlan, y: jax.Array):
    """Pallas-kernel evaluation path; same contract as :func:`cv_errors`.

    Plans without train blocks take the fully fused ``fold_eval`` kernel:
    the hat-row contraction and the fold solves run in one launch and the
    intermediate (N, B) Ê is never materialised. Plans *with* train blocks
    (bias adjust, multiclass) need Ê on every training row for Eq. (15),
    so only the solve stage fuses there: one H·Y matmul, then the
    ``foldsolve`` kernel on the gathered fold blocks. Both routes solve
    I − H_Te directly (Gauss-Jordan with the residual-checked jitter
    fallback) instead of using the plan's cached Cholesky factors — the
    parity tests pin the two paths against each other at ≤1e-5 (fp32).
    """
    from repro.kernels.fold_eval.ops import fold_eval
    from repro.kernels.foldsolve.ops import foldsolve

    squeeze = y.ndim == 1
    if squeeze:
        y = y[:, None]
    te = plan.te_idx
    h_te = plan.h[te[:, :, None], te[:, None, :]]           # (K, m, m)
    y_te = y[te]                                            # (K, m, B)
    if plan.h_tr_te is None:
        t = fold_eval(plan.h[te], h_te, y, y_te)
        y_dot_te = y_te - t
        y_dot_tr = None
    else:
        hi = dot_precision(plan.h.dtype)
        e_hat = y - jnp.matmul(plan.h, y, precision=hi)
        t = foldsolve(h_te, e_hat[te])
        y_dot_te = y_te - t
        e_dot_tr = e_hat[plan.tr_idx] + jnp.einsum(
            "knm,kmb->knb", plan.h_tr_te, t, precision=hi)
        y_dot_tr = y[plan.tr_idx] - e_dot_tr
    if squeeze:
        y_dot_te = y_dot_te[..., 0]
        y_dot_tr = None if y_dot_tr is None else y_dot_tr[..., 0]
    return y_dot_te, y_dot_tr


def binary_dvals(plan: CVPlan, y: jax.Array, adjust_bias: bool = True,
                 *, fused: bool = False):
    """Cross-validated decision values for binary LDA (labels ±1).

    ``y`` is (N,) or (N, B) — a trailing batch dim carries permutations
    (§2.7); all B label vectors share the plan's factorisations.

    With ``adjust_bias`` (paper §2.5) the regression bias b_LR is replaced
    by the LDA bias b_LDA using the cross-validated *training* decision
    values: dval ← ẏ_Te − (μ̂₁ + μ̂₂)/2 where μ̂_l is the mean training
    decision value of class l under the fold's model. This never forms w.
    """
    y = y.astype(plan.h.dtype)
    squeeze = y.ndim == 1
    yb = y[:, None] if squeeze else y                          # (N, B)
    y_dot_te, y_dot_tr = cv_errors(plan, yb, fused=fused)      # (K, m, B)
    if adjust_bias:
        if y_dot_tr is None:
            raise ValueError("plan must be prepared with with_train_block=True")
        y_tr = yb[plan.tr_idx]                                 # (K, N-m, B)
        pos = (y_tr > 0).astype(yb.dtype)
        neg = 1.0 - pos
        mu1 = jnp.sum(y_dot_tr * pos, axis=1) / jnp.maximum(jnp.sum(pos, axis=1), 1.0)
        mu2 = jnp.sum(y_dot_tr * neg, axis=1) / jnp.maximum(jnp.sum(neg, axis=1), 1.0)
        # ẏ − b_LR + b_LDA = ẏ − (μ₁ + μ₂)/2  (projected-class-mean midpoint)
        y_dot_te = y_dot_te - 0.5 * (mu1 + mu2)[:, None, :]
    return y_dot_te[..., 0] if squeeze else y_dot_te


def binary_cv(x: jax.Array, y: jax.Array, folds: Folds, lam: float = 0.0,
              mode: str = "auto", adjust_bias: bool = True):
    """One-shot analytical binary-LDA cross-validation.

    Returns (dvals_te, y_te): per-fold decision values and matching labels,
    both (K, m), ready for ``metrics.binary_accuracy`` / ``metrics.auc``.

    This is the library-level reference implementation; the serving
    equivalent is ``Workload(kind="cv", estimator="binary", ...)`` through
    ``repro.serve.Client`` (bit-identical by the parity tests), which adds
    plan caching, micro-batching, and shape-bucketed compilation.
    """
    plan = prepare(x, folds, lam, mode=mode, with_train_block=adjust_bias)
    dvals = binary_dvals(plan, y, adjust_bias=adjust_bias)
    return dvals, y[folds.te_idx]


# ---------------------------------------------------------------------------
# Serving support: plan fingerprinting + jitted (donated-buffer) eval entry
# points. The plan is label-invariant (§2.7), so a content fingerprint of
# (X, folds, λ, mode) identifies it exactly — the repro.serve.PlanCache key.
# ---------------------------------------------------------------------------

_FINGERPRINT_SAMPLE_CAP = 1 << 20  # elements hashed exactly before sampling

# (id, sample_cap) -> (weakref, digest). jax Arrays are immutable, so
# identity implies content identity while the object is alive; the weakref
# callback evicts the entry on GC so a recycled id can never alias a stale
# digest. The cap is part of the key because it changes the digest for
# arrays above it — memoising on id alone would let a small-cap probe
# poison every later default-cap lookup of the same array (and vice versa).
_fingerprint_memo: dict = {}


def fingerprint(x, *, sample_cap: int = _FINGERPRINT_SAMPLE_CAP) -> str:
    """Stable content digest of an array (shape + dtype + values).

    Arrays up to ``sample_cap`` elements are hashed exactly; larger ones by
    a deterministic strided subsample plus a global f64 checksum — O(cap)
    regardless of dataset size, with astronomically unlikely collisions for
    real feature matrices. The digest depends only on shape/dtype/values
    (plus ``sample_cap`` above it), never on process state — plan keys are
    stable across restarts, which is what lets the disk-backed plan store
    address entries by key. Digests of (immutable) jax arrays are memoised
    by (object identity, cap), so steady-state serving never re-hashes a
    dataset.
    """
    memoable = isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)
    if memoable:
        memo_key = (id(x), sample_cap)
        hit = _fingerprint_memo.get(memo_key)
        if hit is not None and hit[0]() is x:
            return hit[1]
    arr = np.asarray(jax.device_get(x))
    h = hashlib.blake2b(digest_size=16)
    h.update(repr((arr.shape, str(arr.dtype))).encode())
    if arr.size <= sample_cap:
        h.update(np.ascontiguousarray(arr).tobytes())
    else:
        flat = np.ascontiguousarray(arr).reshape(-1)
        stride = -(-arr.size // sample_cap)
        h.update(np.ascontiguousarray(flat[::stride]).tobytes())
        h.update(np.float64(flat.sum(dtype=np.float64)).tobytes())
    digest = h.hexdigest()
    if memoable:
        ref = weakref.ref(x, lambda _, k=memo_key: _fingerprint_memo.pop(k, None))
        _fingerprint_memo[memo_key] = (ref, digest)
    return digest


def plan_key(x, folds: Folds, lam: float, mode: str = "auto",
             with_train_block: bool = True, *, version: int = 0,
             precision: Optional[str] = None) -> tuple:
    """Hashable identity of the :class:`CVPlan` that ``prepare`` would build.

    Both index arrays are fingerprinted: tr_idx is not derivable from
    te_idx in general (leftover samples, custom schemes), and the plan's
    train blocks + bias adjustment depend on it.

    ``version`` is the dataset-registry version number (0 for a freshly
    registered dataset; n+1 after each ``append``/``retire``).
    ``precision`` is the Gram-build precision (None normalises to "fp32");
    plans built at different precisions hold numerically different hat
    matrices and must never alias. Both sit before ``with_train_block``,
    which stays the *last* element — the cache / engine idiom
    ``key[:-1] + (flag,)`` keeps working unchanged. All elements are
    JSON-stable, which is what lets the disk store address entries by key
    across processes.
    """
    from repro.kernels.gram.ops import check_precision
    n, p = x.shape
    if mode == "auto":
        mode = "dual" if p >= n else "primal"
    return (fingerprint(x), fingerprint(folds.te_idx),
            fingerprint(folds.tr_idx), float(lam), mode, int(version),
            check_precision(precision), bool(with_train_block))


#: Plan leaves in flattening order; ``h_tr_te`` is optional (None unless
#: the plan was prepared with train blocks).
PLAN_FIELDS = ("h", "te_idx", "tr_idx", "chol_ih", "h_tr_te")


def plan_to_arrays(plan: CVPlan) -> dict:
    """Host-side ``{leaf name: np.ndarray}`` snapshot of a plan.

    The serialisation codec for :class:`repro.serve.store.PlanStore`: every
    non-None leaf is fetched to host as-is (no dtype laundering — the store
    round-trip must be bit-exact for the rehydrated plan to serve
    bit-identical predictions). A None ``h_tr_te`` is simply omitted.
    """
    out = {}
    for name in PLAN_FIELDS:
        leaf = getattr(plan, name)
        if leaf is not None:
            out[name] = np.asarray(jax.device_get(leaf))
    return out


def plan_from_arrays(arrays) -> CVPlan:
    """Rebuild a :class:`CVPlan` from a :func:`plan_to_arrays` mapping.

    Leaves are placed on the default device; a mapping missing any of the
    four required leaves is rejected (the store treats that as a corrupt
    entry and quarantines it rather than serving a partial plan).
    """
    missing = [n for n in PLAN_FIELDS[:4] if n not in arrays]
    if missing:
        raise ValueError(f"plan arrays missing required leaves {missing}")
    h_tr_te = arrays.get("h_tr_te")
    return CVPlan(
        h=jnp.asarray(arrays["h"]),
        te_idx=jnp.asarray(arrays["te_idx"]),
        tr_idx=jnp.asarray(arrays["tr_idx"]),
        chol_ih=jnp.asarray(arrays["chol_ih"]),
        h_tr_te=None if h_tr_te is None else jnp.asarray(h_tr_te),
    )


def make_eval_binary(adjust_bias: bool = True, donate: bool = False,
                     fused: bool = False):
    """Fresh jitted evaluator ``(plan, y (N, B)) -> dvals (K, m, B)``.

    ``donate=True`` donates the label-batch buffer (permutation chunks are
    single-use) so XLA may alias it into the output — meaningful on
    TPU/GPU; CPU backends ignore donation. Only donate buffers you own:
    the donated array is invalidated for the caller. ``fused=True`` routes
    through the Pallas eval kernels (:func:`cv_errors_fused`). Each call
    returns an independently-cached jit, so callers (the serve engine) can
    count compiles via ``fn._cache_size()``.
    """
    kw = {"donate_argnums": (1,)} if donate else {}
    return jax.jit(
        lambda plan, y: binary_dvals(plan, y, adjust_bias=adjust_bias,
                                     fused=fused), **kw)


def make_eval_cv(donate: bool = False, fused: bool = False):
    """Fresh jitted evaluator ``(plan, y (N, B)) -> ẏ_Te (K, m, B)`` —
    the ridge-regression serving path (Eq. 14 only, no bias adjust)."""
    kw = {"donate_argnums": (1,)} if donate else {}
    return jax.jit(lambda plan, y: cv_errors(plan, y, fused=fused)[0], **kw)


# ---------------------------------------------------------------------------
# Incremental plan updates (streaming data): rank-k update / downdate of a
# cached dual-mode plan when rows arrive or retire, instead of a full
# rebuild. Follows the partition-incremental Gram idea of arXiv 2401.13185
# (fold-wise X^TX blocks admit exact updates with centering corrections),
# specialised to the paper's dual form:
#
#     A = G_c + λI,   S := H − 1/N·11ᵀ = G_c A⁻¹ = I − λA⁻¹
#
# so the *inverse is recoverable from the stored hat matrix* in O(N²):
# A⁻¹ = (I − S)/λ — no refactorisation needed to start an update. Appending
# k rows shifts the column means μ → μ′, which perturbs the old-row block by
# the rank-2 correction R = u1ᵀ + 1uᵀ + (δᵀδ)11ᵀ (δ = μ−μ′, u = X_cδ);
# Woodbury absorbs R, a Schur complement bolts on the k new rows, and
# H′ = I − λA′⁻¹ + 1/N′·11ᵀ. Dropping rows is the principal-submatrix
# inverse identity plus the same mean-shift correction. Total cost is
# O(N²k + NPk) per update — never O(N³) or O(N²P).
#
# Everything here runs in host NumPy (float64) on purpose: update traffic
# arrives with ever-changing N, and a jitted implementation would recompile
# per shape — the serve engine's compile_events stays flat because this
# path never enters XLA. Tolerances vs a from-scratch ``prepare`` rebuild
# are pinned at ≤1e-5 by the parity tests.
# ---------------------------------------------------------------------------


def _np64(a) -> np.ndarray:
    return np.asarray(jax.device_get(a), dtype=np.float64)


def _resolve_update_mode(mode: str, x_shape) -> str:
    if mode == "auto":
        n, p = x_shape
        mode = "dual" if p >= n else "primal"
    if mode != "dual":
        raise ValueError(
            "incremental plan updates require a dual-mode plan (P >= N "
            "regime): the N×N hat matrix determines (G_c + λI)⁻¹ exactly, "
            "which is what the rank-k correction advances. Rebuild primal "
            "plans with prepare() instead.")
    return mode


def _dual_inverse_from_plan(plan: CVPlan, lam: float) -> np.ndarray:
    """Recover A⁻¹ = (G_c + λI)⁻¹ from the stored dual hat matrix, O(N²)."""
    h = _np64(plan.h)
    n = h.shape[0]
    m = (np.eye(n) - (h - 1.0 / n)) / float(lam)
    return 0.5 * (m + m.T)


def _mean_shift_inverse(m: np.ndarray, x_c: np.ndarray,
                        delta: np.ndarray) -> np.ndarray:
    """(A + R)⁻¹ from M = A⁻¹ for the centering correction R.

    R = u1ᵀ + 1uᵀ + (δᵀδ)11ᵀ with u = X_cδ — the exact perturbation of a
    centered Gram when the centering mean shifts by δ. Factor R = W K Wᵀ,
    W = [u, 1], K = [[0,1],[1,δᵀδ]] (det −1, always invertible — robust
    even at δ = 0), and apply Woodbury.
    """
    n = m.shape[0]
    u = x_c @ delta
    w = np.stack([u, np.ones(n)], axis=1)                     # (N, 2)
    c = float(delta @ delta)
    k_inv = np.array([[-c, 1.0], [1.0, 0.0]])                 # K⁻¹
    mw = m @ w                                                # (N, 2)
    core = k_inv + w.T @ mw                                   # (2, 2)
    out = m - mw @ np.linalg.solve(core, mw.T)
    return 0.5 * (out + out.T)


def _append_inverse(m: np.ndarray, x_old: np.ndarray, x_new: np.ndarray,
                    lam: float) -> np.ndarray:
    """A′⁻¹ for [x_old; x_new] (centered at the new mean) from M = A⁻¹."""
    n, k = x_old.shape[0], x_new.shape[0]
    mu = x_old.mean(axis=0)
    mu2 = (n * mu + x_new.sum(axis=0)) / (n + k)
    # Re-center the old block at μ′ (rank-2 Woodbury), then Schur-bolt the
    # k new rows on. The old block A + R equals Z_oZ_oᵀ + λI exactly, with
    # Z_o = x_old − 1μ′ᵀ, so the assembled blocks form (G′_c + λI)⁻¹.
    m_c = _mean_shift_inverse(m, x_old - mu, mu - mu2)
    z_old = x_old - mu2
    z_new = x_new - mu2
    b = z_old @ z_new.T                                       # (N, k)
    c = z_new @ z_new.T + float(lam) * np.eye(k)              # (k, k)
    mb = m_c @ b                                              # (N, k)
    schur = c - b.T @ mb
    schur = 0.5 * (schur + schur.T)
    s_inv = np.linalg.inv(schur)
    s_inv = 0.5 * (s_inv + s_inv.T)
    out = np.empty((n + k, n + k))
    out[:n, :n] = m_c + mb @ s_inv @ mb.T
    out[:n, n:] = -mb @ s_inv
    out[n:, :n] = out[:n, n:].T
    out[n:, n:] = s_inv
    return out


def _downdate_inverse(m: np.ndarray, x_old: np.ndarray,
                      drop: np.ndarray, lam: float) -> np.ndarray:
    """A′⁻¹ for x_old minus ``drop`` rows (centered at the kept mean)."""
    del lam  # identity needs no λ: A_κκ already contains it
    n = x_old.shape[0]
    keep = np.setdiff1d(np.arange(n), drop)
    # Principal-submatrix inverse: (A_κκ)⁻¹ = M_κκ − M_κd (M_dd)⁻¹ M_dκ.
    m_kd = m[np.ix_(keep, drop)]
    m_dd = m[np.ix_(drop, drop)]
    a_kk_inv = m[np.ix_(keep, keep)] - m_kd @ np.linalg.solve(m_dd, m_kd.T)
    x_kept = x_old[keep]
    mu = x_old.mean(axis=0)
    return _mean_shift_inverse(a_kk_inv, x_kept - mu, mu - x_kept.mean(axis=0))


def _finish_plan(m_inv: np.ndarray, lam: float, te: np.ndarray,
                 tr: np.ndarray, with_train_block: bool, dtype) -> CVPlan:
    """H′ = I − λA′⁻¹ + 1/N′·11ᵀ and the per-fold blocks, all in NumPy."""
    n = m_inv.shape[0]
    h = np.eye(n) - float(lam) * m_inv + 1.0 / n
    h = 0.5 * (h + h.T)
    h_te = h[te[:, :, None], te[:, None, :]]                  # (K, m, m)
    chol = np.linalg.cholesky(np.eye(te.shape[1])[None] - h_te)
    h_tr_te = (
        h[tr[:, :, None], te[:, None, :]] if with_train_block else None
    )
    return CVPlan(
        h=jnp.asarray(h, dtype),
        te_idx=jnp.asarray(te, jnp.int32),
        tr_idx=jnp.asarray(tr, jnp.int32),
        chol_ih=jnp.asarray(chol, dtype),
        h_tr_te=None if h_tr_te is None else jnp.asarray(h_tr_te, dtype),
    )


def _complement_folds(te: np.ndarray, n: int) -> np.ndarray:
    """Training side = ascending complement of each fold's test set."""
    k, m = te.shape
    tr = np.empty((k, n - m), dtype=np.int64)
    for i in range(k):
        mask = np.ones(n, dtype=bool)
        mask[te[i]] = False
        tr[i] = np.nonzero(mask)[0]
    return tr


def _check_complement(te: np.ndarray, tr: np.ndarray, n: int) -> None:
    for i in range(te.shape[0]):
        mask = np.ones(n, dtype=bool)
        mask[te[i]] = False
        if not np.array_equal(np.sort(tr[i]), np.nonzero(mask)[0]):
            raise ValueError(
                "incremental fold derivation assumes complement training "
                "sets (every non-test sample trains, as all built-in fold "
                "generators produce); pass folds_delta as a full Folds for "
                "custom schemes")


def _extend_folds(te: np.ndarray, n: int, assign: np.ndarray) -> np.ndarray:
    """New te after appending rows with per-row fold assignment.

    ``assign[j]`` is the fold of appended row j (new sample id n+j), or −1
    for a train-only row (the leftover convention of :mod:`repro.core.folds`
    when K does not divide N). Per-fold counts must stay rectangular.
    """
    k = te.shape[0]
    if assign.ndim != 1:
        raise ValueError("folds_delta assignment must be 1-D (one fold id "
                         "per appended row)")
    if assign.size and (assign.min() < -1 or assign.max() >= k):
        raise ValueError(
            f"fold assignment out of range: got values in "
            f"[{assign.min()}, {assign.max()}], plan has {k} folds")
    tested = assign[assign >= 0]
    counts = np.bincount(tested, minlength=k)
    if counts.max() != counts.min():
        raise ValueError(
            "appending would make per-fold test sizes ragged "
            f"(counts per fold {counts.tolist()}); static shapes require "
            "equal fold sizes — assign equally many rows to every fold "
            "(or -1 for train-only rows)")
    new_ids = n + np.arange(assign.size)
    return np.stack(
        [np.concatenate([te[f], new_ids[assign == f]]) for f in range(k)])


def _drop_folds(te: np.ndarray, n: int, drop: np.ndarray) -> np.ndarray:
    """New te (renumbered over the kept rows) after dropping ``drop``."""
    keep_mask = np.ones(n, dtype=bool)
    keep_mask[drop] = False
    remap = np.cumsum(keep_mask) - 1
    rows = [remap[row[keep_mask[row]]] for row in te]
    sizes = {len(r) for r in rows}
    if len(sizes) != 1:
        raise ValueError(
            "dropping those rows would make per-fold test sizes ragged "
            f"(sizes {sorted(len(r) for r in rows)}); drop equally many "
            "test samples from every fold, or use sliding_window to "
            "backfill the slots with appended rows")
    return np.stack(rows).astype(np.int64)


def _window_folds(te: np.ndarray, n: int, drop: np.ndarray,
                  assign: np.ndarray) -> np.ndarray:
    """New te for drop+append in one move, ragged-checked only at the end.

    Unbalanced drops are fine here (unlike :func:`_drop_folds`) as long as
    the appended rows backfill the holes to equal per-fold sizes. Kept rows
    are renumbered by rank among survivors; appended row j becomes sample
    ``n - len(drop) + j``.
    """
    k_new = assign.size
    rows = []
    for f in range(te.shape[0]):
        kept = te[f][~np.isin(te[f], drop)]
        add = n + np.nonzero(assign == f)[0]
        rows.append(np.concatenate([kept, add]))
    sizes = {len(r) for r in rows}
    if len(sizes) != 1:
        raise ValueError(
            "window advance would make per-fold test sizes ragged "
            f"(sizes {sorted(len(r) for r in rows)}); appended rows must "
            "backfill dropped test slots to equal per-fold counts")
    keep = np.setdiff1d(np.arange(n), drop)
    remap = np.full(n + k_new, -1, dtype=np.int64)
    remap[keep] = np.arange(keep.size)
    remap[n:] = keep.size + np.arange(k_new)
    return np.stack([remap[r] for r in rows]).astype(np.int64)


def _fold_of(te: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Fold membership of each sample id in ``idx`` (−1 if train-only)."""
    out = np.full(idx.shape, -1, dtype=np.int64)
    for f in range(te.shape[0]):
        out[np.isin(idx, te[f])] = f
    return out


def _validate_drop(drop, n: int) -> np.ndarray:
    drop = np.asarray(jax.device_get(drop))
    if drop.ndim != 1 or drop.size == 0:
        raise ValueError("drop_idx must be a non-empty 1-D index array")
    if not np.issubdtype(drop.dtype, np.integer):
        raise ValueError(f"drop_idx must be integer, got dtype {drop.dtype}")
    drop = drop.astype(np.int64)
    if drop.min() < 0 or drop.max() >= n:
        raise ValueError(f"drop_idx out of range for N={n}")
    if np.unique(drop).size != drop.size:
        raise ValueError("drop_idx contains duplicate rows")
    if drop.size >= n:
        raise ValueError("cannot drop every row of the dataset")
    return drop


def _update_inputs(plan: CVPlan, x, lam: float, mode: str):
    """Shared validation; returns (x64, te, tr, with_train_block, dtype)."""
    x_np = _np64(x)
    if x_np.ndim != 2:
        raise ValueError("x must be the 2-D feature matrix the plan was "
                         "built from")
    n = plan.h.shape[0]
    if x_np.shape[0] != n:
        raise ValueError(
            f"x has {x_np.shape[0]} rows but the plan was built over {n} "
            "samples — pass the exact feature matrix behind this plan")
    if not isinstance(lam, (int, float)) or float(lam) <= 0.0:
        raise ValueError("incremental updates require a concrete lam > 0 "
                         "(the dual-mode operating point)")
    _resolve_update_mode(mode, x_np.shape)
    te = np.asarray(jax.device_get(plan.te_idx)).astype(np.int64)
    tr = np.asarray(jax.device_get(plan.tr_idx)).astype(np.int64)
    _check_complement(te, tr, n)
    return x_np, te, tr, plan.h_tr_te is not None, plan.h.dtype


def _coerce_folds_delta(folds_delta, k_new: int):
    """folds_delta is a full Folds (custom schemes) or a per-row assignment."""
    if isinstance(folds_delta, Folds):
        return folds_delta
    assign = np.asarray(jax.device_get(folds_delta))
    if not np.issubdtype(assign.dtype, np.integer):
        raise ValueError("per-row fold assignment must be integer "
                         f"(got dtype {assign.dtype})")
    assign = assign.astype(np.int64).reshape(-1)
    if assign.size != k_new:
        raise ValueError(
            f"fold assignment has {assign.size} entries for "
            f"{k_new} appended rows")
    return assign


def update_plan(plan: CVPlan, x_new, folds_delta, *, x, lam: float,
                mode: str = "dual") -> CVPlan:
    """Advance a dual-mode plan by appending rows — a rank-k correction.

    Args:
      plan: the cached plan for ``x`` (dual mode, built by :func:`prepare`
        or a previous update).
      x_new: (k, P) appended feature rows; the updated dataset is
        ``concat([x, x_new])`` in that order.
      folds_delta: either a per-appended-row fold assignment (1-D int array,
        −1 = train-only leftover) or a full :class:`Folds` over N+k samples
        for custom schemes.
      x: the (N, P) feature matrix the plan was built from (keyword-only —
        the plan itself stores only N×N objects).
      lam: the plan's ridge strength (> 0).
      mode: must resolve to "dual"; primal plans cannot be advanced.

    Returns a new :class:`CVPlan` over N+k samples, equal to
    ``prepare(concat([x, x_new]), new_folds, lam, "dual")`` to ≤1e-5
    without ever rebuilding the Gram or re-entering XLA. Cost O(N²k + NPk).
    """
    x_np, te, tr, wtb, dtype = _update_inputs(plan, x, lam, mode)
    n = x_np.shape[0]
    xn = _np64(x_new)
    if xn.ndim != 2 or xn.shape[1] != x_np.shape[1]:
        raise ValueError(
            f"x_new must be (k, {x_np.shape[1]}) to match the dataset, got "
            f"shape {xn.shape}")
    if folds_delta is None:
        raise ValueError("update_plan needs folds_delta: a fold id per "
                         "appended row (-1 = train-only) or a full Folds")
    delta = _coerce_folds_delta(folds_delta, xn.shape[0])
    if isinstance(delta, Folds):
        te2 = np.asarray(jax.device_get(delta.te_idx)).astype(np.int64)
        tr2 = np.asarray(jax.device_get(delta.tr_idx)).astype(np.int64)
    else:
        te2 = _extend_folds(te, n, delta)
        tr2 = _complement_folds(te2, n + xn.shape[0])
    m = _dual_inverse_from_plan(plan, lam)
    m2 = _append_inverse(m, x_np, xn, lam)
    return _finish_plan(m2, lam, te2, tr2, wtb, dtype)


def downdate_plan(plan: CVPlan, drop_idx, *, x, lam: float,
                  mode: str = "dual") -> CVPlan:
    """Retire rows from a dual-mode plan — the inverse rank-k correction.

    ``drop_idx`` indexes rows of ``x``; surviving rows keep their relative
    order and are renumbered densely (new id = old rank among kept rows),
    so the updated dataset is ``x[keep]`` with ``keep`` sorted. Per-fold
    test sizes must stay rectangular after the drop (drop equally many test
    samples per fold, or train-only rows); use :func:`sliding_window` to
    backfill slots instead. Cost O(N²d + d³).
    """
    x_np, te, tr, wtb, dtype = _update_inputs(plan, x, lam, mode)
    n = x_np.shape[0]
    drop = _validate_drop(drop_idx, n)
    te2 = _drop_folds(te, n, drop)
    tr2 = _complement_folds(te2, n - drop.size)
    m = _dual_inverse_from_plan(plan, lam)
    m2 = _downdate_inverse(m, x_np, drop, lam)
    return _finish_plan(m2, lam, te2, tr2, wtb, dtype)


def sliding_window(plan: CVPlan, x_new, drop_idx, *, x, lam: float,
                   mode: str = "dual", folds_delta=None) -> CVPlan:
    """Append + drop in one correction — the streaming steady state.

    The window advances: ``drop_idx`` rows retire and ``x_new`` rows arrive,
    with N (and therefore every downstream eval shape) unchanged whenever
    ``len(x_new) == len(drop_idx)``. By default each appended row inherits
    the fold slot of a dropped row (matched in sorted drop order), so the
    fold geometry — and the jitted eval cache — is preserved exactly; pass
    ``folds_delta`` to re-assign instead. The updated dataset is
    ``concat([x[keep], x_new])``.
    """
    x_np, te, tr, wtb, dtype = _update_inputs(plan, x, lam, mode)
    n = x_np.shape[0]
    drop = _validate_drop(drop_idx, n)
    xn = _np64(x_new)
    if xn.ndim != 2 or xn.shape[1] != x_np.shape[1]:
        raise ValueError(
            f"x_new must be (k, {x_np.shape[1]}) to match the dataset, got "
            f"shape {xn.shape}")
    n_kept = n - drop.size
    if folds_delta is None:
        if xn.shape[0] != drop.size:
            raise ValueError(
                "sliding_window without folds_delta requires "
                "len(x_new) == len(drop_idx) so appended rows can inherit "
                f"the dropped rows' fold slots (got {xn.shape[0]} new vs "
                f"{drop.size} dropped)")
        assign = _fold_of(te, np.sort(drop))
        te2 = _window_folds(te, n, drop, assign)
        tr2 = _complement_folds(te2, n_kept + xn.shape[0])
    else:
        delta = _coerce_folds_delta(folds_delta, xn.shape[0])
        if isinstance(delta, Folds):
            te2 = np.asarray(jax.device_get(delta.te_idx)).astype(np.int64)
            tr2 = np.asarray(jax.device_get(delta.tr_idx)).astype(np.int64)
        else:
            te2 = _window_folds(te, n, drop, delta)
            tr2 = _complement_folds(te2, n_kept + xn.shape[0])
    m = _dual_inverse_from_plan(plan, lam)
    m_dropped = _downdate_inverse(m, x_np, drop, lam)
    keep = np.setdiff1d(np.arange(n), drop)
    m2 = _append_inverse(m_dropped, x_np[keep], xn, lam)
    return _finish_plan(m2, lam, te2, tr2, wtb, dtype)
