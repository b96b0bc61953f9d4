"""Sync drivers over the one-Workload API.

The serving surface is :class:`repro.serve.workload.Workload` — one
versioned, eagerly-validated spec (``kind``: ``cv | permutation | rsa |
tune | grid | update``) against a registered dataset handle or an inline
:class:`~repro.serve.workload.DatasetSpec`, executed by
:func:`repro.serve.workload.run_workloads` and fronted by
:class:`repro.serve.client.Client` (which picks the sync, thread-queue,
or asyncio transport by construction).

The pre-0.1 request vocabulary (``CVRequest``, ``PermutationRequest``,
``RSARequest``, ``TuneRequest`` and their ``to_workload()`` shims) was
**removed at 0.3** per the deprecation timeline announced in README "One
API"; importing any of those names raises :class:`ImportError` with a
pointer at the README migration table ("Migration from the request
classes").

:func:`serve` is the synchronous batch driver: it groups workloads by
plan identity, coalesces same-plan label queries through the
:class:`~repro.serve.batching.MicroBatcher` (one padded jitted eval per
(plan, estimator, static-options) group), and un-pads per-request
results. :class:`EngineServer` wraps the same driver in a thread-backed
queue so concurrent submitters get futures while their queries ride
shared micro-batches; the asyncio counterpart (with streamed responses)
lives in :mod:`repro.serve.aio`.
"""

from __future__ import annotations

import queue as queue_mod
import threading
import time
from concurrent.futures import Future
from typing import Optional, Sequence

# reprolint: monotonic-time
# (Gather deadlines / batch_wait stamps — the PR 6 bug class.)

from repro.serve.engine import CVEngine
from repro.serve.trace import attach_trace, trace_of
from repro.serve.workload import (  # noqa: F401  (re-exported compat surface)
    CVResponse,
    DatasetSpec,
    GridResponse,
    PermutationResponse,
    RSAResponse,
    TuneResponse,
    Workload,
    as_workload,
    run_workloads,
)

__all__ = [
    "DatasetSpec",
    "CVResponse",
    "PermutationResponse",
    "RSAResponse",
    "TuneResponse",
    "GridResponse",
    "serve",
    "EngineServer",
]

#: Names removed at 0.3 (the deprecated request shims). Kept here only so
#: the ImportError can say where the replacement lives.
_REMOVED_AT_0_3 = ("CVRequest", "PermutationRequest", "RSARequest", "TuneRequest", "Request")


def __getattr__(name: str):
    if name in _REMOVED_AT_0_3:
        raise ImportError(
            f"{name} was removed at 0.3 — construct a repro.serve.Workload "
            "(or use repro.serve.Client) instead; the field-by-field mapping "
            "is in the README migration table ('Migration from the request "
            "classes')."
        )
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# ---------------------------------------------------------------------------
# Synchronous driver
# ---------------------------------------------------------------------------


def serve(engine: CVEngine, requests: Sequence[Workload]) -> list:
    """Serve a batch of Workloads; responses align with ``requests``.

    Thin alias of :func:`repro.serve.workload.run_workloads`: same-plan CV
    label queries are coalesced into one padded jitted eval per (plan,
    estimator, static-options) group; plans are fetched once per distinct
    dataset; ``kind="update"`` workloads against the same handle coalesce
    into one rank-k plan correction.
    """
    return run_workloads(engine, requests)


# ---------------------------------------------------------------------------
# Thread-backed queue for concurrent submitters
# ---------------------------------------------------------------------------


class EngineServer:
    """Background worker that drains a request queue into micro-batches.

    Submitters (any thread) get a Future per Workload; the worker
    collects whatever is queued — up to
    ``max_batch`` requests, waiting at most ``max_wait_ms`` after the
    first — and serves the whole batch through :func:`serve`, so
    concurrent clients' queries coalesce onto shared plans and shared
    padded evals.
    """

    def __init__(self, engine: CVEngine, max_batch: int = 64, max_wait_ms: float = 2.0):
        self.engine = engine
        self.max_batch = max_batch
        self.max_wait_s = max_wait_ms / 1e3
        self._queue: "queue_mod.Queue" = queue_mod.Queue()
        self._stop = threading.Event()
        self._submit_lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self.batches_served = 0
        self.requests_served = 0

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "EngineServer":
        if self._thread is not None:
            raise RuntimeError("server already started")
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True, name="cv-engine-server")
        self._thread.start()
        return self

    def stop(self) -> None:
        # The lock orders every in-flight submit() before the stop flag:
        # anything enqueued before the flag is visible to the worker's
        # exit condition (stop AND queue-empty), so it gets served; any
        # later submit raises instead of landing on a dead queue.
        with self._submit_lock:
            self._stop.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        while True:  # belt-and-braces: never strand a future
            try:
                _, fut = self._queue.get_nowait()
            except queue_mod.Empty:
                break
            fut.set_exception(RuntimeError("server stopped before serving"))

    def __enter__(self) -> "EngineServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- client side -------------------------------------------------------

    def submit(self, request: Workload) -> Future:
        with self._submit_lock:
            if self._stop.is_set() or self._thread is None:
                raise RuntimeError("server is not running")
            # Tracing starts on the *submit* side so queue time is a real,
            # measured stage (batch_wait) instead of silently inflating
            # eval time. The trace rides the workload object across the
            # thread boundary (context vars do not).
            # A trace made here is finished when the worker sets the
            # future; one handed in is finished by its maker.
            tracer = self.engine.tracer
            fut: Future = Future()
            if tracer.enabled and trace_of(request) is None:
                trace = tracer.trace()
                attach_trace(request, trace)
                fut.add_done_callback(lambda _f, t=trace: tracer.finish(t))
            trace = trace_of(request)
            if trace is not None:
                trace.mark_enqueue()
            self._queue.put((request, fut))
            return fut

    # -- worker side -------------------------------------------------------

    def _drain_batch(self):
        try:
            first = self._queue.get(timeout=0.05)
        except queue_mod.Empty:
            return []
        batch = [first]
        t_end = time.monotonic() + self.max_wait_s
        while len(batch) < self.max_batch:
            remaining = t_end - time.monotonic()
            if remaining <= 0:
                break
            try:
                batch.append(self._queue.get(timeout=remaining))
            except queue_mod.Empty:
                break
        return batch

    def _run(self) -> None:
        while not (self._stop.is_set() and self._queue.empty()):
            batch = self._drain_batch()
            if not batch:
                continue
            requests = [req for req, _ in batch]
            futures = [fut for _, fut in batch]
            # One dequeue timestamp for the whole batch: every member's
            # submit->here latency is its batch_wait stage.
            now = time.perf_counter()
            for req in requests:
                trace = trace_of(req)
                if trace is not None:
                    trace.note_dequeue(now)
            self.engine.metrics.observe("gather_window_occupancy", len(batch))
            try:
                # Per-entry result-or-error: one bad workload must not abort
                # sibling submitters coalesced into the same batch.
                responses = run_workloads(self.engine, requests, return_errors=True)
            except Exception as e:  # noqa: BLE001 - fanned out
                for fut in futures:
                    fut.set_exception(e)
                continue
            for fut, resp in zip(futures, responses):
                if isinstance(resp, Exception):
                    fut.set_exception(resp)
                else:
                    fut.set_result(resp)
            self.batches_served += 1
            self.requests_served += len(batch)
