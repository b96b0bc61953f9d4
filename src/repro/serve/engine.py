"""CVEngine: plan-cached, shape-bucketed analytical-CV evaluation.

The engine is the multi-tenant core of ``repro.serve``. It owns

  * a :class:`~repro.serve.cache.PlanCache` — one
    :class:`~repro.core.fastcv.CVPlan` per (dataset × folds × λ × mode),
    LRU-evicted under a byte budget, so repeated requests against the same
    features never re-factorise — optionally backed by a durable
    :class:`~repro.serve.store.PlanStore` tier (``plan_store`` config):
    cache misses read-through from disk before rebuilding, fresh builds
    persist write-behind (``save_plans``), so a restarted replica
    warm-boots with zero plan builds;
  * a **dataset registry** — :meth:`CVEngine.register` fingerprints a
    dataset once and returns a
    :class:`~repro.serve.workload.DatasetHandle`; workloads carry the
    handle instead of re-shipping the feature matrix, evicted plans
    rebuild transparently, and :meth:`datasets` exposes residency /
    pinning / traffic per registration. The registry is *mutable and
    versioned*: :meth:`append` / :meth:`retire` /
    :meth:`update_dataset` advance a dataset to a version n+1 handle by
    rank-k plan correction (:func:`repro.core.fastcv.update_plan`),
    while version n stays servable — in-flight workloads pin it
    (:meth:`retain_version`) — until :meth:`release`;
  * the CV *jitted evaluators*, drawn from the least-squares **estimator
    registry** (:mod:`repro.serve.workload`): one compiled program per
    (eval family × static options × shape bucket), created lazily but
    exactly once per engine so jit caches — and hence compile counts —
    are observable. Binary LDA, multi-class LDA, ridge, and multi-target
    ridge are registrations; :meth:`eval_estimator` serves any newly
    registered model with zero engine changes. Permutation-null metrics
    and RSA scoring keep their own jit families;
  * an **RDM memo** (:class:`repro.rsa.rdm.RDMCache`): empirical RDMs
    keyed by (plan, labels fingerprint), so repeat model scoring against
    the same data skips the fold solves (``stats()["rdm_hits"]``);
  * *shape buckets* for the label-batch dimension: every batch is padded up
    to a static bucket size before hitting jit, so an engine serving ragged
    traffic compiles at most ``len(buckets)`` programs per eval path and
    zero after warm-up.

Plan builds route the O(N²P) centered-Gram hot-spot through the Pallas
``gram`` kernel on TPU (``gram_impl="auto"``/"pallas") or through
``distributed_gram`` when a mesh is configured (``gram_impl="distributed"``,
which also shards permutation batches over the mesh's data axes).

:meth:`CVEngine.warmup` turns the lazy caches into an explicit readiness
API: it pre-builds (and optionally pins) the plan for a dataset spec and
pre-compiles the bucketed eval family for a set of tasks, so first real
traffic hits zero plan builds and zero compiles. The chunk-level
``observed_*`` / ``null_*`` methods expose the permutation machinery at
sub-request granularity — the streaming front-end
(:mod:`repro.serve.aio`) drives them to emit incremental null chunks.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from collections.abc import Mapping
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import fastcv, metrics, multiclass, tuning
from repro.core import permutation as perm_lib
from repro.core.folds import Folds
from repro.kernels.common import default_fused
from repro.rsa import compare as rsa_compare
from repro.rsa import rdm as rsa_rdm
from repro.serve.batching import DEFAULT_BUCKETS, MicroBatcher, as_folds, bucket_size
from repro.serve.cache import PlanCache
from repro.serve.obs import BUCKET_FAMILIES, METRICS, MetricsRegistry
from repro.serve.store import PlanStore
from repro.serve.trace import STAGES, Tracer
from repro.serve.workload import DatasetHandle, get_estimator

__all__ = ["EngineConfig", "CVEngine", "DatasetHandle"]

_GRAM_IMPLS = ("auto", "xla", "pallas", "distributed")
_PRECISIONS = ("fp32", "bf16_gram")  # mirrors repro.kernels.gram.ops.PRECISIONS
_WARMUP_TASKS = ("binary", "ridge", "multiclass", "permutation", "rsa")


@dataclasses.dataclass
class _DatasetRecord:
    """Registry entry behind a :class:`DatasetHandle`.

    Keeps the actual feature matrix and folds so plans evicted under cache
    pressure can be rebuilt from the handle alone — clients never re-ship
    the bytes.

    ``version``/``n_appended`` mirror the handle (the registry is the
    source of truth for the mutable-dataset lineage). ``refs`` counts
    in-flight workload batches pinning this version
    (:meth:`CVEngine.retain_version`); ``retired`` marks a version whose
    :meth:`CVEngine.release` was deferred until those refs drain.
    """

    handle: DatasetHandle
    x: jax.Array
    folds: Folds
    lam: float
    mode: str
    served: int = 0
    last_used: float = 0.0  # wall-clock (time.time) — display only, never a deadline
    version: int = 0
    n_appended: int = 0
    refs: int = 0
    retired: bool = False
    drop_store: bool = False


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine knobs.

    cache_bytes: PlanCache byte budget.
    gram_impl:   "auto" (Pallas kernel on TPU, plain XLA elsewhere),
                 "xla", "pallas", or "distributed" (requires ``mesh``).
    mesh:        optional jax Mesh, or a mapping of axis name to size
                 (``{"data": 2, "model": 2}``: a mesh over the first
                 ``prod(sizes)`` devices); enables distributed plan builds
                 and mesh-sharded permutation batches.
    feature_axis / perm_axes: mesh axis names for the feature-sharded Gram
                 reduction and the permutation fan-out respectively.
    donate:      donate label-batch buffers to the jitted evals. Off by
                 default (None/False): donation lets XLA alias the batch
                 into the eval's output (single-use permutation chunks
                 never round-trip), meaningful on TPU/GPU. With donate on,
                 batches the engine doesn't own are defensively copied
                 before hitting an exact shape bucket (no padding = no
                 implicit copy), so a caller's array is never invalidated
                 behind its back; internal paths pass ``owned=True`` and
                 donate end-to-end.
    fused:       route CV evals through the fused Pallas fold-eval
                 kernels instead of the XLA reference composite. None
                 (default) = auto: on where Pallas compiles natively
                 (TPU), off elsewhere (interpret mode is Python-slow).
                 Plans without train blocks get the fully fused
                 ``fold_eval`` kernel (no (N, B) Ê materialisation);
                 train-block paths fuse the fold-solve stage.
    precision:   Gram/hat build precision: "fp32" (default; the working
                 dtype end-to-end) or "bf16_gram" (dual-mode Gram built
                 from bf16 inputs with f32 accumulation, all solves full
                 precision — see :mod:`repro.kernels.gram.ops` for the
                 error bound). Part of the plan key: the two precisions
                 never share cached plans.
    buckets:     static label-batch sizes; ragged batches pad up to these.
    plan_store:  optional directory for the durable plan tier
                 (:class:`repro.serve.store.PlanStore`): cache misses try
                 a verified disk read before the O(N²P) rebuild.
    save_plans:  with ``plan_store``: write-behind every freshly built
                 plan to the store (off = read-only warm-boot tier).
    store_bytes: plan-store byte budget (GC evicts oldest entries over
                 it, never those pinned in the in-memory cache).
    """

    cache_bytes: int = 512 << 20
    gram_impl: str = "auto"
    mesh: Optional[object] = None
    feature_axis: str = "model"
    perm_axes: tuple = ("data",)
    donate: Optional[bool] = None
    fused: Optional[bool] = None
    precision: str = "fp32"
    buckets: Sequence[int] = DEFAULT_BUCKETS
    plan_store: Optional[str] = None
    save_plans: bool = False
    store_bytes: int = 4 << 30

    def __post_init__(self):
        if isinstance(self.mesh, Mapping):
            sizes = tuple(int(v) for v in self.mesh.values())
            mesh = jax.make_mesh(sizes, tuple(self.mesh),
                                 devices=jax.devices()[:math.prod(sizes)])
            object.__setattr__(self, "mesh", mesh)
        object.__setattr__(self, "perm_axes", tuple(self.perm_axes))
        if self.gram_impl not in _GRAM_IMPLS:
            raise ValueError(f"gram_impl must be one of {_GRAM_IMPLS}")
        if self.gram_impl == "distributed" and self.mesh is None:
            raise ValueError("gram_impl='distributed' requires a mesh")
        if self.save_plans and not self.plan_store:
            raise ValueError("save_plans=True requires a plan_store directory")
        if self.precision not in _PRECISIONS:
            raise ValueError(f"precision must be one of {_PRECISIONS}")
        if self.precision != "fp32" and self.gram_impl == "distributed":
            raise ValueError(
                "precision='bf16_gram' is not supported with "
                "gram_impl='distributed' (the feature-sharded reduction "
                "has no mixed-precision path yet)")


class CVEngine:
    """Multi-tenant analytical-CV evaluation engine."""

    # Concurrency contract, machine-checked by reprolint RL004: the
    # thread server (EngineServer) and the asyncio gather loop both drive
    # one engine, so the lifetime stat counters increment under _lock —
    # a lost `+= b` here silently skews capacity accounting.
    _GUARDED_BY = {
        "plans_built": "_lock",
        "plans_updated": "_lock",
        "labels_evaluated": "_lock",
    }

    def __init__(self, config: Optional[EngineConfig] = None):
        self.config = config or EngineConfig()
        self.cache = PlanCache(self.config.cache_bytes)
        self.store = (
            PlanStore(self.config.plan_store, byte_budget=self.config.store_bytes)
            if self.config.plan_store
            else None
        )
        self.rdm_cache = rsa_rdm.RDMCache()
        self.metrics = MetricsRegistry()
        self.tracer = Tracer(registry=self.metrics)
        self._declare_metrics()
        self.batcher = MicroBatcher(self.config.buckets, metrics=self.metrics)
        self._donate = bool(self.config.donate)
        self._fused = default_fused() if self.config.fused is None else bool(self.config.fused)
        # Eval paths are created lazily but exactly once per static
        # signature and held forever: the dict entry IS the jit cache the
        # no-recompile guarantee rests on. CV evals come from the
        # least-squares estimator registry (repro.serve.workload): one
        # jitted program per (eval_key, static options, donate, fused) —
        # registered estimators sharing an eval_key (ridge / ridge_multi)
        # share it. donate/fused sit in the key so flipping either
        # (set_donate, a reconfigured engine) can never serve a stale
        # program with the wrong aliasing or kernel route.
        self._evals = {}  # (eval_key, static opts, donate, fused) -> jit
        self._perm_binary = {}  # (metric, adjust_bias) -> jit -> (B,)
        self._mesh_null = {}  # (metric, adjust_bias) -> mesh jit -> (B,)
        self._perm_multiclass = {}  # num_classes -> jit -> (B,)
        self._rsa_pairs = {}  # (dissim, adjust_bias, donate, fused) -> jit
        self._rsa_score = {}  # method -> jit[(emp, models) -> (M,)]
        self._rsa_null = {}  # method -> jit[(emp, models, perms) -> (M,T)]
        self._datasets = {}  # handle key -> _DatasetRecord
        self._lock = threading.Lock()  # guards the stat counters below
        self.plans_built = 0
        self.plans_updated = 0
        self.labels_evaluated = 0

    def _declare_metrics(self) -> None:
        """Register the central :data:`repro.serve.obs.METRICS` table.

        The table is the single declaration of every metric name, kind
        and label-key set (reprolint RL003 checks call sites against it);
        this method contributes only *behavior*: the callback behind each
        gauge. Cache / jit / memo health is exported through callback
        gauges over the existing counters — the registry is a view, never
        a second copy, which is what keeps ``stats()`` bit-for-bit
        identical to its pre-observability schema. Stage histograms get
        every stage label pre-declared so the ``/v1/metrics`` exposition
        lists the full vocabulary before any traffic.
        """
        m = self.metrics
        gauge_sources = {
            "plan_cache_hits": lambda: self.cache.stats.hits,
            "plan_cache_misses": lambda: self.cache.stats.misses,
            "plan_cache_evictions": lambda: self.cache.stats.evictions,
            "plan_cache_oversized": lambda: self.cache.stats.oversized,
            "plan_cache_bytes_in_use": lambda: self.cache.stats.bytes_in_use,
            "plan_store_hits": lambda: self.store.stats.hits if self.store else 0,
            "plan_store_misses": lambda: self.store.stats.misses if self.store else 0,
            "plan_store_writes": lambda: self.store.stats.writes if self.store else 0,
            "plan_store_bytes": lambda: self.store.stats.bytes_in_store if self.store else 0,
            "compile_events": self.compile_count,
            "rdm_hits": lambda: self.rdm_cache.hits,
            "plans_built": lambda: self.plans_built,
            "plans_updated": lambda: self.plans_updated,
            "labels_evaluated": lambda: self.labels_evaluated,
            "datasets_registered": lambda: len(self._datasets),
        }
        for name, spec in METRICS.items():
            kind = spec["kind"]
            if kind == "counter":
                m.counter(name, spec["help"], labels=spec["labels"])
            elif kind == "histogram":
                m.histogram(
                    name,
                    spec["help"],
                    buckets=BUCKET_FAMILIES[spec["buckets"]],
                    labels=spec["labels"],
                )
            else:
                # KeyError here means METRICS declares a gauge this engine
                # supplies no callback for — fail at construction, loudly.
                m.gauge(name, spec["help"], fn=gauge_sources.pop(name))
        if gauge_sources:
            raise RuntimeError(
                f"gauge callbacks without a METRICS declaration: {sorted(gauge_sources)}"
            )
        stage_hist = m.get("stage_latency_seconds")
        for stage in STAGES:
            stage_hist.declare(stage=stage)

    def enable_tracing(self, ring: int = 256) -> None:
        """Turn on request-scoped span tracing (``serve_cv --metrics``).

        Every subsequent workload gets a span tree (decode → encode),
        attached to its response as ``timings`` and kept in a bounded ring
        of ``ring`` traces (``GET /v1/trace``, :meth:`Tracer.summary`).
        Tracing adds per-stage clock reads, a ``block_until_ready`` and a
        ``repro.<stage>`` profiler annotation per span, and a ``gc``
        callback that times collector pauses — leave it off for
        peak-throughput serving.
        """
        self.tracer.enable(ring=ring)

    def disable_tracing(self) -> None:
        """Back to zero-overhead mode (finished traces stay in the ring)."""
        self.tracer.disable()

    def set_donate(self, donate: bool) -> None:
        """Flip label-batch donation at runtime.

        Safe mid-traffic: donate is part of every eval-cache key, so a
        non-donating program compiled before the flip can never be served
        for a donating request (or vice versa) — the regression that
        motivated keying the caches on it.
        """
        self._donate = bool(donate)

    # ------------------------------------------------------------------
    # Plans
    # ------------------------------------------------------------------

    def plan(
        self,
        x: jax.Array,
        folds: Folds,
        lam: float,
        mode: str = "auto",
        with_train_block: bool = True,
        version: int = 0,
    ):
        """Fetch-or-build the plan for (x, folds, λ). Returns (key, plan).

        Lookup order: memory (PlanCache) → disk (PlanStore, when
        configured) → build. A plan *with* the train block is a superset
        of the one without (same H, same factors, extra H_{Tr,Te}), so a
        ridge request is happily served from a cached bias-adjust plan.
        ``version`` is the dataset-registry version the key is minted
        under (0 for unregistered / freshly registered data)."""
        with self.tracer.span("cache_lookup"):
            key = fastcv.plan_key(x, folds, lam, mode, with_train_block,
                                  version=version, precision=self.config.precision)
            if not with_train_block:
                superset = key[:-1] + (True,)
                plan = self.cache.get(superset)
                if plan is not None:
                    return superset, plan
        plan, _ = self.cache.get_or_build(
            key,
            lambda: self._build_plan(x, folds, lam, mode, with_train_block, key=key),
            fetch=self._store_fetch(key),
        )
        return key, plan

    def _store_fetch(self, key):
        """Read-through closure for the disk tier (None when no store).

        ``store_load`` is its own trace stage: warm-boot budgets care
        whether a miss cost a disk read or an O(N²P) rebuild.
        """
        if self.store is None:
            return None

        def fetch():
            with self.tracer.span("store_load"):
                return self.tracer.sync(self.store.load(key))

        return fetch

    def _build_plan(self, x, folds, lam, mode, with_train_block, key=None):
        # Top-level span (not nested under cache_lookup) so the build cost
        # lands in its own stage_latency_seconds series — plan_build is the
        # budget the next perf PR (kernel fusion) is judged against.
        with self.tracer.span("plan_build"):
            n, p = x.shape
            resolved = ("dual" if p >= n else "primal") if mode == "auto" else mode
            gram = self._build_gram(x) if resolved == "dual" else None
            plan = self.tracer.sync(
                fastcv.prepare(
                    x, folds, lam, mode=resolved, with_train_block=with_train_block,
                    gram=gram, precision=self.config.precision
                )
            )
        with self._lock:
            self.plans_built += 1
        if key is not None and self.store is not None and self.config.save_plans:
            # Write-behind: snapshot now, commit off the request path. The
            # current pin set shields those entries from this write's GC.
            self.store.save_async(key, plan, protect=self.cache.pinned_keys())
        return plan

    def flush_store(self) -> None:
        """Join outstanding write-behind plan saves (shutdown path);
        no-op without a configured store."""
        if self.store is not None:
            self.store.flush()

    def _build_gram(self, x):
        impl = self.config.gram_impl
        if impl == "auto":
            impl = "pallas" if jax.default_backend() == "tpu" else "xla"
        if impl == "xla":
            return None  # prepare() computes it inline (honouring precision)
        if impl == "pallas":
            from repro.kernels.gram.ops import centered_gram

            return centered_gram(x, precision=self.config.precision)
        from repro.core.distributed import distributed_gram

        return distributed_gram(x, self.config.mesh, feature_axis=self.config.feature_axis)

    # ------------------------------------------------------------------
    # Dataset registry: register once, serve by handle
    # ------------------------------------------------------------------

    def register(self, x: jax.Array, folds, lam: float, mode: str = "auto") -> DatasetHandle:
        """Register a dataset; returns a :class:`DatasetHandle`.

        The handle is keyed by the same content fingerprint the plan cache
        uses (``fastcv.plan_key``), so registering identical bytes twice
        yields the same handle. Workloads carry the handle instead of
        re-shipping the feature matrix; the engine keeps the features so a
        plan evicted under byte pressure rebuilds transparently on next
        use. Handle-scoped operations: :meth:`warmup` (accepts a handle),
        :meth:`pin`/:meth:`unpin` (via ``handle.key``), :meth:`evict`, and
        the :meth:`datasets` introspection view.
        """
        folds = as_folds(folds)
        key = fastcv.plan_key(x, folds, lam, mode, True, version=0,
                              precision=self.config.precision)
        rec = self._datasets.get(key)
        if rec is None:
            if self.config.mesh is not None:
                # features over the feature axis, as the sharded Gram reads them
                x = jax.device_put(x, NamedSharding(
                    self.config.mesh, PartitionSpec(None, self.config.feature_axis)))
            handle = DatasetHandle(
                key=key, n=int(x.shape[0]), p=int(x.shape[1]), lam=float(lam), mode=mode
            )
            rec = self._datasets[key] = _DatasetRecord(handle, x, folds, float(lam), mode)
        return rec.handle

    def dataset_record(self, handle: DatasetHandle) -> _DatasetRecord:
        rec = self._datasets.get(handle.key)
        if rec is None:
            raise KeyError(f"dataset handle {handle.key[0][:8]} is not registered on this engine")
        return rec

    def resolve(self, dataset, with_train_block: bool = True):
        """(key, plan) for a :class:`DatasetHandle` or inline spec.

        Handles resolve through the registry (rebuilding the plan if it
        was evicted); anything with ``x`` / ``folds`` / ``lam`` attributes
        — e.g. :class:`repro.serve.workload.DatasetSpec` — is planned
        directly.
        """
        if isinstance(dataset, DatasetHandle):
            rec = self.dataset_record(dataset)
            rec.served += 1
            rec.last_used = time.time()
            return self.plan(
                rec.x,
                rec.folds,
                rec.lam,
                mode=rec.mode,
                with_train_block=with_train_block,
                version=rec.version,
            )
        folds = as_folds(dataset.folds)
        mode = getattr(dataset, "mode", "auto")
        return self.plan(
            dataset.x,
            folds,
            dataset.lam,
            mode=mode,
            with_train_block=with_train_block,
            version=getattr(dataset, "version", 0),
        )

    def evict(self, handle: DatasetHandle, *, deregister: bool = False) -> bool:
        """Drop a registered dataset's cached plans (both train-block
        variants); with ``deregister`` also forget the registration."""
        rec = self._datasets.get(handle.key)
        removed = self.cache.remove(handle.key)
        no_train = handle.key[:-1] + (False,)
        removed = self.cache.remove(no_train) or removed
        if deregister and rec is not None:
            del self._datasets[handle.key]
        return removed

    # ------------------------------------------------------------------
    # Mutable versioned datasets: append / retire / sliding window
    # ------------------------------------------------------------------

    def update_dataset(
        self,
        handle: DatasetHandle,
        *,
        x_new=None,
        drop_idx=None,
        folds_delta=None,
    ) -> DatasetHandle:
        """Advance a registered dataset to version n+1 and return its handle.

        Exactly one logical operation per call, picked by the arguments:
        ``x_new`` alone appends rows (round-robin over folds by default —
        requires ``len(x_new) % K == 0`` — or per ``folds_delta``),
        ``drop_idx`` alone retires rows, both together slide the window
        (appended rows inherit the dropped rows' fold slots unless
        ``folds_delta`` says otherwise). Dual-mode plans advance by the
        rank-k correction in :func:`repro.core.fastcv.update_plan` — no
        Gram rebuild, no XLA entry; primal plans fall back to a from-scratch
        rebuild with the same fold evolution.

        The previous version stays registered and servable (in-flight
        workloads pin it via :meth:`retain_version`) until
        :meth:`release` — the two versions have distinct plan keys, so the
        PlanCache/PlanStore never conflate them.
        """
        # reprolint: host-path
        # (Update-group coalescing: everything until the plan correction
        # runs on host; jnp is only entered through asarray/device slices.)
        rec = self.dataset_record(handle)
        if x_new is None and drop_idx is None:
            raise ValueError(
                "update_dataset needs x_new (append), drop_idx (retire), or both (window)"
            )
        n, p = int(rec.x.shape[0]), int(rec.x.shape[1])
        k = 0 if x_new is None else int(x_new.shape[0])
        drop = None
        if drop_idx is not None:
            drop = np.asarray(jax.device_get(drop_idx)).reshape(-1).astype(np.int64)
        d = 0 if drop is None else int(drop.size)
        if k and not d and folds_delta is None:
            n_folds = rec.folds.k
            if k % n_folds:
                raise ValueError(
                    f"appending {k} rows to a {n_folds}-fold dataset without "
                    "folds_delta would leave ragged folds; pass a per-row fold "
                    f"assignment or append a multiple of {n_folds} rows"
                )
            folds_delta = np.arange(k, dtype=np.int64) % n_folds
        op = "window" if (k and d) else ("append" if k else "retire")
        resolved = rec.mode
        if resolved == "auto":
            resolved = "dual" if p >= n else "primal"
        _, plan = self.plan(
            rec.x, rec.folds, rec.lam, mode=rec.mode, with_train_block=True, version=rec.version
        )
        with self.tracer.span("plan_update"):
            if resolved == "dual":
                if op == "window":
                    plan2 = fastcv.sliding_window(
                        plan,
                        x_new,
                        drop,
                        x=rec.x,
                        lam=rec.lam,
                        mode="dual",
                        folds_delta=folds_delta,
                    )
                elif op == "append":
                    plan2 = fastcv.update_plan(
                        plan, x_new, folds_delta, x=rec.x, lam=rec.lam, mode="dual"
                    )
                else:
                    plan2 = fastcv.downdate_plan(plan, drop, x=rec.x, lam=rec.lam, mode="dual")
                folds2 = Folds.with_indices(plan2.te_idx, plan2.tr_idx, n=n - d + k)
            else:
                folds2 = self._updated_folds(rec, k, drop, folds_delta)
                plan2 = None
            x2 = rec.x
            if d:
                keep = np.setdiff1d(np.arange(n), drop)
                x2 = x2[jnp.asarray(keep)]
            if k:
                # Grows the registered device copy in place of a host
                # round-trip of the full X: window traffic repeats the
                # same (n, p) signature, so this concatenate is a
                # steady-state jit-cache hit, not per-call churn.
                x2 = jnp.concatenate(  # reprolint: ignore[RL001] -- steady-state shapes repeat
                    [x2, jnp.asarray(x_new, dtype=x2.dtype)]
                )
            new_version = rec.version + 1
            new_key = fastcv.plan_key(x2, folds2, rec.lam, resolved, True,
                                      version=new_version,
                                      precision=self.config.precision)
            if plan2 is None:
                plan2 = self._build_plan(x2, folds2, rec.lam, resolved, True, key=new_key)
            else:
                self.cache.get_or_build(new_key, lambda: plan2)
                if self.store is not None and self.config.save_plans:
                    self.store.save_async(new_key, plan2, protect=self.cache.pinned_keys())
        new_handle = DatasetHandle(
            key=new_key,
            n=int(x2.shape[0]),
            p=p,
            lam=rec.lam,
            mode=resolved,
            version=new_version,
            n_appended=rec.n_appended + k,
        )
        rec2 = self._datasets.get(new_key)
        if rec2 is None:
            rec2 = self._datasets[new_key] = _DatasetRecord(
                new_handle,
                x2,
                folds2,
                rec.lam,
                resolved,
                version=new_version,
                n_appended=rec.n_appended + k,
            )
        with self._lock:
            self.plans_updated += 1
        self.metrics.inc("plan_updates_total", op=op)
        self.metrics.observe("plan_update_rank", float(k + d))
        return rec2.handle

    def _updated_folds(self, rec: _DatasetRecord, k: int, drop, folds_delta) -> Folds:
        """Fold evolution for the primal (full-rebuild) fallback — the same
        geometry the dual fast path derives from the corrected plan."""
        if isinstance(folds_delta, Folds):
            return folds_delta
        te = np.asarray(jax.device_get(rec.folds.te_idx)).astype(np.int64)
        n = int(rec.x.shape[0])
        d = 0 if drop is None else int(drop.size)
        if k and d:
            if folds_delta is None:
                if k != d:
                    raise ValueError(
                        "sliding-window update without folds_delta requires "
                        "len(x_new) == len(drop_idx) so appended rows can "
                        f"inherit fold slots (got {k} new vs {d} dropped)"
                    )
                assign = fastcv._fold_of(te, np.sort(drop))
            else:
                assign = np.asarray(jax.device_get(folds_delta)).reshape(-1).astype(np.int64)
            te2 = fastcv._window_folds(te, n, drop, assign)
        elif k:
            assign = np.asarray(jax.device_get(folds_delta)).reshape(-1).astype(np.int64)
            te2 = fastcv._extend_folds(te, n, assign)
        else:
            te2 = fastcv._drop_folds(te, n, drop)
        tr2 = fastcv._complement_folds(te2, n - d + k)
        return Folds.with_indices(
            jnp.asarray(te2, dtype=jnp.int32), jnp.asarray(tr2, dtype=jnp.int32), n=n - d + k
        )

    def append(self, handle: DatasetHandle, x_new, folds_delta=None) -> DatasetHandle:
        """Append rows to a registered dataset → version n+1 handle.

        Sugar for :meth:`update_dataset`; see it for fold-assignment rules
        and version-pinning semantics.
        """
        return self.update_dataset(handle, x_new=x_new, folds_delta=folds_delta)

    def retire(self, handle: DatasetHandle, idx) -> DatasetHandle:
        """Retire rows of a registered dataset → version n+1 handle."""
        return self.update_dataset(handle, drop_idx=idx)

    def release(self, handle: DatasetHandle, *, drop_store: bool = False) -> bool:
        """Release a dataset version: deregister it and drop its cached
        plans once no in-flight workload pins it.

        With refs outstanding the version is only marked ``retired`` and
        the purge happens on the last :meth:`release_version`. With
        ``drop_store`` the durable :class:`PlanStore` entry is removed too
        (a clean removal — stale versions are *not* quarantined); without
        it the store entry stays for forensic warm-boots. Returns True if
        the purge ran now, False if deferred (or unknown handle).
        """
        rec = self._datasets.get(handle.key)
        if rec is None:
            return False
        rec.retired = True
        rec.drop_store = drop_store
        if rec.refs > 0:
            return False
        self._purge(handle.key, drop_store)
        return True

    def retain_version(self, key) -> None:
        """Pin a dataset version for an in-flight workload batch.

        Tolerant no-op for keys that are not registered versions (inline
        specs, raw plan keys)."""
        rec = self._datasets.get(key)
        if rec is not None:
            rec.refs += 1

    def release_version(self, key) -> None:
        """Drop an in-flight pin; purges the version if it was released
        (retired) while pinned. Tolerant no-op on unknown keys."""
        rec = self._datasets.get(key)
        if rec is None:
            return
        rec.refs = max(0, rec.refs - 1)
        if rec.retired and rec.refs == 0:
            self._purge(key, rec.drop_store)

    def _purge(self, key, drop_store: bool) -> None:
        """Forget a dataset version: registry entry, both cached plan
        variants, and (optionally) the durable store entry — cleanly, so
        eviction of a stale version never quarantines its checkpoint."""
        self._datasets.pop(key, None)
        self.cache.unpin(key)
        self.cache.remove(key)
        no_train = key[:-1] + (False,)
        self.cache.unpin(no_train)
        self.cache.remove(no_train)
        if drop_store and self.store is not None:
            self.store.remove(key)
            self.store.remove(no_train)

    def datasets(self) -> tuple:
        """Introspection view: one dict per registered dataset."""
        out = []
        for key, rec in self._datasets.items():
            plan = self.cache.peek(key) or self.cache.peek(key[:-1] + (False,))
            out.append(
                {
                    "handle": rec.handle,
                    "n": rec.handle.n,
                    "p": rec.handle.p,
                    "lam": rec.lam,
                    "mode": rec.mode,
                    "version": rec.version,
                    "n_appended": rec.n_appended,
                    "served": rec.served,
                    "resident": plan is not None,
                    "pinned": key in self.cache.pinned_keys(),
                    "nbytes": plan.nbytes if plan is not None else 0,
                }
            )
        return tuple(out)

    # -- pinning (PlanCache passthrough) -------------------------------

    def pin(self, key) -> bool:
        """Exempt a cached plan from eviction; see :meth:`PlanCache.pin`.

        Accepts a raw plan key or a :class:`DatasetHandle`.
        """
        return self.cache.pin(key.key if isinstance(key, DatasetHandle) else key)

    def unpin(self, key) -> bool:
        return self.cache.unpin(key.key if isinstance(key, DatasetHandle) else key)

    # ------------------------------------------------------------------
    # Warm-up: pre-build plans, pre-compile the bucketed eval family
    # ------------------------------------------------------------------

    def warmup(
        self,
        spec,
        tasks: Sequence[str] = ("binary",),
        buckets: Optional[Sequence[int]] = None,
        *,
        num_classes: int = 0,
        metric: str = "accuracy",
        adjust_bias: bool = True,
        dissimilarity: str = "accuracy",
        comparison: str = "spearman",
        num_model_rdms: int = 0,
        pin: bool = False,
    ) -> dict:
        """Pre-build the plan for ``spec`` and pre-compile eval programs.

        ``spec`` is anything with ``x`` / ``folds`` / ``lam`` (and
        optionally ``mode``) attributes — e.g. :class:`repro.serve.api
        .DatasetSpec`. ``tasks`` selects eval families from
        {"binary", "ridge", "multiclass", "permutation", "rsa"};
        ``buckets`` the label-batch sizes to compile (default: every
        configured bucket; values are canonicalised via ``bucket_size``).
        After a warm-up covering the shapes traffic will hit,
        ``compile_count()`` stays flat — first real requests pay only the
        O(K·m²) fold solves.

        The "rsa" task compiles the pairwise-contrast path for
        (``dissimilarity``, ``adjust_bias``); with ``num_model_rdms`` > 0
        it also compiles the model-scoring + permutation-null programs for
        ``comparison`` at every null bucket (the model count M is a static
        shape, so pass the M real traffic will carry).

        With ``pin=True`` the built plan is pinned in the cache (never
        LRU-evicted, excluded from budget pressure) until ``unpin``.
        Returns a summary dict (plan_key, buckets, compiles, pinned).
        """
        unknown = [t for t in tasks if t not in _WARMUP_TASKS]
        if unknown:
            raise ValueError(f"unknown warmup tasks {unknown}; expected {_WARMUP_TASKS}")
        if "multiclass" in tasks and num_classes < 2:
            raise ValueError("warmup of 'multiclass' needs num_classes >= 2")
        if isinstance(spec, DatasetHandle):
            spec = self.dataset_record(spec)
        key, plan = self.resolve(spec, with_train_block=True)
        wanted = sorted(
            {bucket_size(b, self.config.buckets) for b in (buckets or self.config.buckets)}
        )
        n = int(spec.x.shape[0])
        y_bin = jnp.where(jnp.arange(n) % 2 == 0, -1.0, 1.0).astype(plan.h.dtype)
        y_mc = (jnp.arange(n, dtype=jnp.int32) % max(num_classes, 2)).astype(jnp.int32)
        outs = []
        if "permutation" in tasks:
            outs.append(self.observed_binary(plan, y_bin, metric=metric, adjust_bias=adjust_bias))
            if num_classes >= 2:
                outs.append(self.observed_multiclass(plan, y_mc, num_classes=num_classes))
        for b in wanted:
            if "binary" in tasks:
                cols = jnp.tile(y_bin[:, None], (1, b))
                outs.append(self.eval_binary(plan, cols, adjust_bias))
            if "ridge" in tasks:
                outs.append(self.eval_ridge(plan, jnp.tile(y_bin[:, None], (1, b))))
            if "multiclass" in tasks:
                rows = jnp.tile(y_mc[None, :], (b, 1))
                outs.append(self.eval_multiclass(plan, rows, num_classes))
            if "permutation" in tasks:
                draws = perm_lib.permutation_draws(jax.random.PRNGKey(0), y_bin, b)
                outs.append(
                    self.null_binary(plan, draws, metric=metric, adjust_bias=adjust_bias)
                )
                if num_classes >= 2:  # mirrors the observed_multiclass gate above
                    draws = perm_lib.permutation_draws(jax.random.PRNGKey(0), y_mc, b)
                    outs.append(self.null_multiclass(plan, draws, num_classes=num_classes))
            if "rsa" in tasks:
                cols = jnp.tile(y_bin[:, None], (1, b))
                outs.append(self.eval_rsa_pairs(plan, cols, dissimilarity, adjust_bias))
        if "rsa" in tasks and num_model_rdms > 0:
            if num_classes < 2:
                raise ValueError("rsa model-scoring warmup needs num_classes >= 2")
            rdm0 = jnp.zeros((num_classes, num_classes), plan.h.dtype)
            models0 = jnp.zeros((num_model_rdms,) + rdm0.shape, plan.h.dtype)
            outs.append(self.score_rdms(rdm0, models0, comparison))
            for b in wanted:
                perms0 = perm_lib.permutation_indices(jax.random.PRNGKey(0), num_classes, b)
                outs.append(self.null_rdm_scores(rdm0, models0, perms0, comparison))
        jax.block_until_ready(outs)
        pinned = self.cache.pin(key) if pin else False
        return {
            "plan_key": key,
            "buckets": tuple(wanted),
            "compiles": self.compile_count(),
            "pinned": pinned,
        }

    # ------------------------------------------------------------------
    # Shape-bucketed jitted evaluation
    # ------------------------------------------------------------------

    @staticmethod
    def _strip_train(plan: fastcv.CVPlan) -> fastcv.CVPlan:
        """Canonicalise a plan for train-block-free eval paths.

        A no-train-block request may be served from the cached *superset*
        plan (see :meth:`plan`), whose ``h_tr_te`` is an array instead of
        None — a different pytree structure, which would retrace the jitted
        eval and recompute the unused Eq. 15 train solves. Stripping the
        block restores one structure (and one compiled program) per shape.
        """
        if plan.h_tr_te is None:
            return plan
        return dataclasses.replace(plan, h_tr_te=None)

    def _pad_cols(self, y: jax.Array, *, owned: bool = False) -> tuple[jax.Array, int]:
        b = y.shape[1]
        padded = bucket_size(b, self.config.buckets)
        if padded > b:
            y = jnp.pad(y, ((0, 0), (0, padded - b)))
        elif self._donate and not owned:
            # Exact-bucket batches pass through without the implicit copy
            # padding provides; a donating eval would invalidate the
            # caller's array behind its back. Copy defensively — internal
            # single-use batches (MicroBatcher groups, permutation chunks)
            # declare owned=True and donate end-to-end instead.
            y = jnp.copy(y)
        return y, b

    def _pad_rows(self, y: jax.Array, *, owned: bool = False) -> tuple[jax.Array, int]:
        b = y.shape[0]
        padded = bucket_size(b, self.config.buckets)
        if padded > b:
            y = jnp.concatenate([y, jnp.broadcast_to(y[:1], (padded - b,) + y.shape[1:])], 0)
        elif self._donate and not owned:
            y = jnp.copy(y)  # same exact-bucket aliasing hazard as _pad_cols
        return y, b

    def eval_estimator(self, plan: fastcv.CVPlan, y: jax.Array, estimator: str,
                       owned: bool = False, **opts):
        """Shape-bucketed eval through the least-squares estimator registry.

        ``estimator`` names a registered
        :class:`~repro.serve.workload.LeastSquaresSpec`; the spec supplies
        the targets encoding, batch layout, jitted-eval factory, and
        train-block requirement — this one method is the engine's entire
        CV eval surface, so a newly registered estimator (multi-target
        ridge, optimal-scoring variants, …) is served, bucketed, and
        compile-counted with zero engine changes.

        ``owned=True`` declares the batch single-use engine property (the
        MicroBatcher's coalesced groups): with donation on it skips the
        exact-bucket defensive copy and lets the eval consume the buffer.
        """
        spec = get_estimator(estimator)
        opts = spec.resolve_opts(opts)
        if not spec.needs_train(opts):
            plan = self._strip_train(plan)
        batch, squeeze = spec.encode(y, plan.h.dtype, opts)
        owned = owned or batch is not y  # encode copied -> engine owns it
        key = (spec.eval_key, spec.static_key(opts), self._donate, self._fused)
        fn = self._evals.get(key)
        if fn is None:
            fn = self._evals[key] = spec.make_eval(opts, self._donate, self._fused)
        if spec.layout == "columns":
            padded, b = self._pad_cols(batch, owned=owned)
            with self.tracer.span("eval"):
                out = self.tracer.sync(fn(plan, padded)[..., :b])
            with self._lock:
                self.labels_evaluated += b
            return out[..., 0] if squeeze else out
        padded, b = self._pad_rows(batch, owned=owned)
        self._count_step2(plan, padded.shape[0], opts.get("num_classes", 0))
        with self.tracer.span("eval"):
            out = self.tracer.sync(fn(plan, padded)[:b])
        with self._lock:
            self.labels_evaluated += b
        return out[0] if squeeze else out

    def eval_binary(self, plan: fastcv.CVPlan, y: jax.Array, adjust_bias: bool = True) -> jax.Array:
        """Binary-LDA decision values. y: (N,) or (N, B) ±1 labels."""
        return self.eval_estimator(plan, y, "binary", adjust_bias=adjust_bias)

    def eval_ridge(self, plan: fastcv.CVPlan, y: jax.Array) -> jax.Array:
        """Exact CV ridge predictions ẏ_Te. y: (N,) or (N, B) responses."""
        return self.eval_estimator(plan, y, "ridge")

    def eval_multiclass(
        self, plan: fastcv.CVPlan, y: jax.Array, num_classes: int, owned: bool = False
    ) -> jax.Array:
        """Multi-class LDA CV predictions. y: int (N,) or (B, N)."""
        return self.eval_estimator(plan, y, "multiclass", owned=owned, num_classes=num_classes)

    # ------------------------------------------------------------------
    # RSA serving (pairwise-contrast RDMs + model scoring, §4.2)
    # ------------------------------------------------------------------

    def eval_rsa_pairs(
        self,
        plan: fastcv.CVPlan,
        cols: jax.Array,
        dissimilarity: str = "accuracy",
        adjust_bias: bool = True,
        owned: bool = False,
    ) -> jax.Array:
        """Pairwise-contrast dissimilarities. cols: (N, B) ±1/0 columns.

        Contrast columns are just label columns, so they ride the same
        bucketed column path as binary/ridge evals: padded (all-zero)
        columns score to a harmless constant and are sliced away.
        ``owned`` as in :meth:`eval_estimator`.
        """
        cache_key = (dissimilarity, adjust_bias, self._donate, self._fused)
        fn = self._rsa_pairs.get(cache_key)
        if fn is None:
            fn = self._rsa_pairs[cache_key] = rsa_rdm.make_eval_pairs(
                dissimilarity, adjust_bias, donate=self._donate, fused=self._fused
            )
        if not adjust_bias:
            plan = self._strip_train(plan)
        cast = cols.astype(plan.h.dtype)
        owned = owned or cast is not cols  # dtype cast copied -> engine owns it
        cols = cast
        padded, b = self._pad_cols(cols, owned=owned)
        with self.tracer.span("eval"):
            out = self.tracer.sync(fn(plan, padded)[:b])
        with self._lock:
            self.labels_evaluated += b
        return out

    def score_rdms(
        self, empirical: jax.Array, model_rdms: jax.Array, method: str = "spearman"
    ) -> jax.Array:
        """(M,) model-RDM scores through the engine's jitted scorer."""
        fn = self._rsa_score.get(method)
        if fn is None:
            fn = self._rsa_score[method] = rsa_compare.make_compare(method)
        with self.tracer.span("eval"):
            return self.tracer.sync(fn(empirical, model_rdms))

    def null_rdm_scores(
        self,
        empirical: jax.Array,
        model_rdms: jax.Array,
        perms: jax.Array,
        method: str = "spearman",
    ) -> jax.Array:
        """(M, B) null scores for explicit condition permutations (B, C).

        The permutation batch pads up to a shape bucket like every other
        batched path, so chunked (streaming) nulls never recompile after
        one warm-up per chunk bucket.
        """
        with self.tracer.span("null_chunk"):
            fn = self._rsa_null.get(method)
            if fn is None:
                fn = self._rsa_null[method] = rsa_compare.make_compare_null(method)
            padded, b = self._pad_rows(perms, owned=True)
            return self.tracer.sync(fn(empirical, model_rdms, padded)[:, :b])

    def compare_rdms(
        self,
        empirical: jax.Array,
        model_rdms: jax.Array,
        method: str = "spearman",
        n_perm: int = 0,
        key: Optional[jax.Array] = None,
    ):
        """Score model RDMs against an empirical RDM; optional null.

        Returns (scores (M,), null (M, n_perm) | None, p (M,) | None).
        Null permutations are generated at the bucketed size (like the CV
        permutation path), so arbitrary client-chosen n_perm never
        compiles a fresh program after one warm-up per shape bucket.
        """
        scores = self.score_rdms(empirical, model_rdms, method)
        if n_perm <= 0:
            return scores, None, None
        t_gen = bucket_size(n_perm, self.config.buckets)
        if key is None:
            key = jax.random.PRNGKey(0)
        # Draw generation and the p-value are null-distribution work: they
        # count toward the null_chunk stage like the CV permutation path.
        with self.tracer.span("null_chunk"):
            perms = self.tracer.sync(
                perm_lib.permutation_indices(key, empirical.shape[0], t_gen)
            )
        null = self.null_rdm_scores(empirical, model_rdms, perms, method)
        with self.tracer.span("null_chunk"):
            null = null[:, :n_perm]
            p = self.tracer.sync(
                (1.0 + jnp.sum(null >= scores[:, None], axis=1)) / (1.0 + n_perm)
            )
        return scores, null, p

    # ------------------------------------------------------------------
    # Permutation serving (Algorithms 1 & 2 against a cached plan)
    # ------------------------------------------------------------------

    def _perm_binary_fn(self, metric: str, adjust_bias: bool):
        """jit[(plan, y_perm (B, N)) -> (B,) metrics] of permuted label rows."""
        fn = self._perm_binary.get((metric, adjust_bias))
        if fn is None:

            def _eval(plan, y_perm):
                return perm_lib.null_binary_metrics(plan, y_perm, metric=metric,
                                                    adjust_bias=adjust_bias)

            fn = self._perm_binary[(metric, adjust_bias)] = jax.jit(_eval)
        return fn

    def _mesh_null_fn(self, metric: str, adjust_bias: bool):
        """The mesh analogue of :meth:`_perm_binary_fn`: one program
        (``jit__mesh_null``) that pads the draws to whole shards, evaluates
        them over ``perm_axes``, gathers the null and slices it back."""
        fn = self._mesh_null.get((metric, adjust_bias))
        if fn is None:
            from repro.core.distributed import mesh_null_program

            fn = self._mesh_null[(metric, adjust_bias)] = mesh_null_program(
                self.config.mesh, metric=metric, perm_axes=self.config.perm_axes,
                adjust_bias=adjust_bias)
        return fn

    def _count_step2(self, plan: fastcv.CVPlan, rows: int, num_classes: int) -> None:
        """Count the C×C step-2 eigenproblems of one multi-class dispatch:
        one per (padded label vector, fold). ``num_classes`` 0: none."""
        if num_classes:
            self.metrics.inc("step2_solves_total", rows * plan.te_idx.shape[0],
                             solver=multiclass.step2_solver(num_classes))

    def _perm_multiclass_fn(self, num_classes: int):
        """jit[(plan, y_rows (B, N) int) -> (B,) accuracies] of label rows."""
        fn = self._perm_multiclass.get(num_classes)
        if fn is None:

            def _eval(plan, y_rows):
                preds = multiclass.batch_predict(plan, y_rows, num_classes)
                y_te = y_rows[:, plan.te_idx]  # (B, K, m)
                return jax.vmap(metrics.multiclass_accuracy)(preds, y_te)

            fn = self._perm_multiclass[num_classes] = jax.jit(_eval)
        return fn

    def observed_binary(
        self,
        plan: fastcv.CVPlan,
        y: jax.Array,
        *,
        metric: str = "accuracy",
        adjust_bias: bool = True,
    ) -> jax.Array:
        """Observed (unpermuted) binary metric through the permutation path."""
        # The span covers the dispatch preamble (dtype cast, padding) too —
        # each is a device dispatch that would otherwise show up as an
        # untraced gap in the span timeline.
        with self.tracer.span("eval"):
            if not adjust_bias:
                plan = self._strip_train(plan)
            y = y.astype(plan.h.dtype)
            fn = self._perm_binary_fn(metric, adjust_bias)
            return self.tracer.sync(fn(plan, self._pad_rows(y[None], owned=True)[0])[0])

    def null_binary(
        self,
        plan: fastcv.CVPlan,
        y_perm: jax.Array,
        *,
        metric: str = "accuracy",
        adjust_bias: bool = True,
        requested: Optional[int] = None,
    ) -> jax.Array:
        """Null metrics for a (B, N) batch of permuted label rows → (B,).

        The chunk-level building block under both :meth:`permutation_binary`
        and the streaming front-end. On a mesh-configured engine the batch
        shards over ``perm_axes`` in one jitted program per shape (padded
        up to a whole number of shards, gathered, trimmed back) — so
        *streamed* null chunks use the mesh exactly like monolithic
        requests, with identical draws. Locally, the batch pads up to a
        shape bucket and repeats never recompile.

        ``requested`` is how many leading rows of ``y_perm`` were asked for
        (all of them by default); the rest, and the rows the path pads on,
        count as padding in ``null_pad_draws_total``.
        """
        b = y_perm.shape[0]
        with self.tracer.span("null_chunk"):
            if not adjust_bias:
                plan = self._strip_train(plan)
            y_perm = y_perm.astype(plan.h.dtype)
            if self.config.mesh is not None:
                from repro.core.distributed import whole_shards

                path = "mesh"
                rows = whole_shards(b, self.config.mesh, self.config.perm_axes)
                out = self._mesh_null_fn(metric, adjust_bias)(plan, y_perm)
            else:
                path = "local"
                fn = self._perm_binary_fn(metric, adjust_bias)
                padded = self._pad_rows(y_perm, owned=True)[0]
                rows = padded.shape[0]
                out = fn(plan, padded)[:b]
            self.tracer.sync(out)
        self._count_null(path, b if requested is None else requested, rows)
        return out

    def _count_null(self, path: str, requested: int, rows: int) -> None:
        """Count one null dispatch: ``requested`` draws asked for, of
        ``rows`` evaluated (the rest padding)."""
        self.metrics.inc("null_draws_total", requested, path=path)
        self.metrics.inc("null_pad_draws_total", rows - requested, path=path)
        with self._lock:
            self.labels_evaluated += requested

    def observed_multiclass(
        self, plan: fastcv.CVPlan, y: jax.Array, *, num_classes: int
    ) -> jax.Array:
        with self.tracer.span("eval"):
            fn = self._perm_multiclass_fn(num_classes)
            padded = self._pad_rows(y[None], owned=True)[0]
            self._count_step2(plan, padded.shape[0], num_classes)
            return self.tracer.sync(fn(plan, padded)[0])

    def null_multiclass(
        self, plan: fastcv.CVPlan, y_rows: jax.Array, *, num_classes: int,
        requested: Optional[int] = None,
    ) -> jax.Array:
        """Multi-class analogue of :meth:`null_binary`: (B, N) permuted
        label rows → (B,) accuracies, on the local path."""
        with self.tracer.span("null_chunk"):
            fn = self._perm_multiclass_fn(num_classes)
            padded, b = self._pad_rows(y_rows, owned=True)
            self._count_step2(plan, padded.shape[0], num_classes)
            out = self.tracer.sync(fn(plan, padded)[:b])
        self._count_null("local", b if requested is None else requested, padded.shape[0])
        return out

    def permutation_binary(
        self,
        plan: fastcv.CVPlan,
        y: jax.Array,
        n_perm: int,
        key: jax.Array,
        *,
        metric: str = "accuracy",
        adjust_bias: bool = True,
    ) -> perm_lib.PermutationResult:
        """Algorithm 1 against a cached plan: observed + null + p-value.

        With a mesh configured, the permutation batch shards over the
        mesh's ``perm_axes``; otherwise it runs through the bucketed local
        eval path. Either way the draws are generated at the bucket size,
        so repeats never recompile and both paths evaluate the same draws.
        """
        observed = self.observed_binary(plan, y, metric=metric, adjust_bias=adjust_bias)
        # Generate directly at the bucket size: permutation_draws jits on
        # static T, so bucketing T here is what keeps arbitrary
        # client-chosen n_perm from compiling a fresh generator each time.
        # Draw generation and the p-value are null-distribution work, so
        # they count toward the null_chunk stage (timings() sums same-name
        # top-level spans) — leaving them untraced would break the
        # stage-sum ≈ end-to-end acceptance invariant.
        t_gen = bucket_size(n_perm, self.config.buckets)
        with self.tracer.span("null_chunk"):
            # the labels the null reads, shuffled: cast before the draws
            y = y.astype(plan.h.dtype)
            if self.config.mesh is not None:
                # every chip draws them all and keeps its shard: no transfer
                key, y = jax.device_put(
                    (key, y), NamedSharding(self.config.mesh, PartitionSpec()))
            y_perm = self.tracer.sync(perm_lib.permutation_draws(key, y, t_gen))
        # this API's contract (and the multiclass path) counts the
        # *requested* draws only
        null = self.null_binary(plan, y_perm, metric=metric, adjust_bias=adjust_bias,
                                requested=n_perm)[:n_perm]
        with self.tracer.span("null_chunk"):
            p = self.tracer.sync(perm_lib.p_value(observed, null))
        return perm_lib.PermutationResult(observed, null, p)

    def permutation_multiclass(
        self,
        plan: fastcv.CVPlan,
        y: jax.Array,
        n_perm: int,
        key: jax.Array,
        *,
        num_classes: int,
    ) -> perm_lib.PermutationResult:
        """Algorithm 2 under permutations against a cached plan."""
        observed = self.observed_multiclass(plan, y, num_classes=num_classes)
        t_gen = bucket_size(n_perm, self.config.buckets)
        with self.tracer.span("null_chunk"):
            y_rows = self.tracer.sync(perm_lib.permutation_draws(key, y, t_gen))
        null = self.null_multiclass(plan, y_rows, num_classes=num_classes,
                                    requested=n_perm)[:n_perm]
        with self.tracer.span("null_chunk"):
            p = self.tracer.sync(perm_lib.p_value(observed, null))
        return perm_lib.PermutationResult(observed, null, p)

    # ------------------------------------------------------------------
    # Tuning (routed to the eigendecomposition-based LOO machinery)
    # ------------------------------------------------------------------

    def tune(self, x: jax.Array, y: jax.Array, lambdas=None, criterion: str = "mse"):
        with self.tracer.span("eval"):
            # RidgeTuneResult is a NamedTuple, i.e. a pytree — sync whole.
            return self.tracer.sync(tuning.tune_ridge(x, y, lambdas=lambdas, criterion=criterion))

    # ------------------------------------------------------------------
    # Observability
    # ------------------------------------------------------------------

    def compile_count(self) -> int:
        """Total jit cache entries across every eval path this engine owns.

        Stable compile_count across requests == zero recompiles."""
        fns = (
            list(self._evals.values())
            + list(self._perm_binary.values())
            + list(self._perm_multiclass.values())
            + list(self._rsa_pairs.values())
            + list(self._rsa_score.values())
            + list(self._rsa_null.values())
        )
        return int(sum(f._cache_size() for f in fns))

    def dataset_stats(self) -> dict:
        """JSON-safe per-registered-dataset breakdown.

        Keyed by the first 12 hex chars of the content fingerprint (the
        same prefix ``/v1/datasets`` shows). ``plan_bytes`` counts the
        resident plan (either train-block variant), 0 when evicted;
        ``last_used`` is a wall-clock timestamp (0.0 = never served by
        handle). This is the handle-scoped view behind
        ``stats()["per_dataset"]`` and the bench_serve residency row.
        """
        out = {}
        for key, rec in self._datasets.items():
            plan = self.cache.peek(key) or self.cache.peek(key[:-1] + (False,))
            out[str(key[0])[:12]] = {
                "n": rec.handle.n,
                "p": rec.handle.p,
                "version": rec.version,
                "n_appended": rec.n_appended,
                "served": rec.served,
                "plan_bytes": plan.nbytes if plan is not None else 0,
                "resident": plan is not None,
                "pinned": key in self.cache.pinned_keys(),
                "last_used": rec.last_used,
            }
        return out

    def stats(self) -> dict:
        """Flat engine/cache counters plus a ``per_dataset`` breakdown.

        The pre-observability keys (cache stats, plans_built,
        labels_evaluated, compiles, datasets_registered, rdm_hits,
        rdm_entries) are preserved bit-for-bit — the metrics registry
        reads *these* counters through callback gauges, never the other
        way round. The ``store_*`` keys are always present (zero without
        a configured plan store) so dashboards and the restart-smoke
        assertions never branch on configuration. ``per_dataset`` is
        :meth:`dataset_stats`.
        """
        s = self.cache.stats.as_dict()
        st = self.store.stats if self.store is not None else None
        s.update(
            plans_built=self.plans_built,
            plans_updated=self.plans_updated,
            labels_evaluated=self.labels_evaluated,
            compiles=self.compile_count(),
            datasets_registered=len(self._datasets),
            rdm_hits=self.rdm_cache.hits,
            rdm_entries=len(self.rdm_cache),
            store_hits=st.hits if st else 0,
            store_misses=st.misses if st else 0,
            store_writes=st.writes if st else 0,
            store_bytes=st.bytes_in_store if st else 0,
        )
        s["per_dataset"] = self.dataset_stats()
        return s
