"""repro.serve — plan-cached analytical-CV serving engine, one workload API.

The paper's economics (§2.7: the hat matrix and fold factorisations depend
on features only) have the exact shape of a serving workload — expensive
label-invariant state, cheap per-request evaluation. This package
productises that behind a single declarative surface:

  workload  Workload — one versioned, eagerly-validated spec (kind:
            cv | permutation | rsa | tune | grid | update) against a
            registered DatasetHandle or inline DatasetSpec;
            LeastSquaresSpec — the estimator registry under which binary
            LDA, multi-class LDA, ridge, and multi-target ridge are
            registrations, not engine forks; run_workloads /
            stream_workload drivers; TrafficLog.
  client    Client — submit/stream/gather over a transport chosen by
            construction (sync or asyncio).
  cache     PlanCache — LRU CVPlan store under a byte budget, with
            admission control for plans larger than the whole budget and
            pin/unpin for warm, never-evicted plans.
  store     PlanStore — durable disk tier under the cache: atomic
            content-addressed plan checkpoints with integrity-verified
            loads, corrupt-entry quarantine, and byte-budget GC, so a
            restarted replica warm-boots with zero plan builds.
  engine    CVEngine — mutable versioned dataset registry (register →
            version-0 handle; append/retire/update_dataset → version n+1
            by rank-k plan correction, old versions pinned by in-flight
            workloads until release), cached plans + shape-bucketed
            jitted eval paths from the estimator registry, RDM
            memoisation, and an explicit warmup() readiness API
            (replayable from recorded traffic).
  batching  MicroBatcher — coalesce ragged same-plan label queries.
  aio       AsyncEngineServer — asyncio front-end with gather-window
            micro-batching and streamed permutation/RSA responses.
  http      HTTPEdge — the HTTP/SSE wire over the async server (Workload
            JSON in, result-or-error batches and SSE ProgressEvent
            streams out), plus the HTTPClient transport mirror — and the
            ``GET /v1/metrics`` (Prometheus text) / ``GET /v1/trace``
            exposition routes.
  obs       MetricsRegistry — zero-dependency counters, gauges, and
            fixed-bucket histograms over the whole request path, rendered
            in Prometheus text format.
  trace     Tracer / Trace / Span — request-scoped stage timing
            (decode → validate → plan_build → cache_lookup → store_load →
            batch_wait → eval → null_chunk → encode) attached to responses
            as an optional ``timings`` dict; off by default, zero overhead
            when disabled (``engine.enable_tracing()``).

Entry point: ``python -m repro.launch.serve_cv`` (``--http PORT`` for the
network edge).
"""

from repro.serve.aio import AsyncEngineServer, ProgressEvent  # noqa: F401
from repro.serve.batching import MicroBatcher, bucket_size  # noqa: F401
from repro.serve.cache import CacheStats, PlanCache  # noqa: F401
from repro.serve.client import Client  # noqa: F401
from repro.serve.engine import CVEngine, EngineConfig  # noqa: F401
from repro.serve.http import (  # noqa: F401
    EdgeThread,
    HTTPClient,
    HTTPEdge,
    WireError,
)
from repro.serve.obs import MetricsRegistry  # noqa: F401
from repro.serve.store import PlanStore, StoreStats  # noqa: F401
from repro.serve.trace import STAGES, Span, Trace, Tracer  # noqa: F401
from repro.serve.workload import (  # noqa: F401
    WORKLOAD_SCHEMA_VERSION,
    CVResponse,
    DatasetHandle,
    DatasetSpec,
    GridResponse,
    LeastSquaresSpec,
    PermutationResponse,
    RSAResponse,
    TrafficLog,
    TuneResponse,
    UpdateResponse,
    Workload,
    as_workload,
    estimators,
    get_estimator,
    register_estimator,
    run_workloads,
    stream_workload,
)
