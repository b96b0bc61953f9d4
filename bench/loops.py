"""The one traffic generator: a mix file's parameters in, a measured window out.

A mix (``bench/traffic/<mix>.json``) is data. Its ``loop`` picks one of
two drivers:

``closed``
    One in-process :class:`repro.serve.Client` (sync transport) runs
    *visits* back to back until the window closes. A visit is the mix's
    ``visit`` list of steps against one subject, cycling through the
    subjects set-up simulated, with a fresh seed per visit. Each step is a
    module of its own, ``bench/steps/<step>.py`` (:mod:`bench.cells`):
    ``register`` (the dataset), ``cv``, ``permutation`` (with ``n_perm``
    draws) and ``release`` (the engine forgets the dataset and its plans,
    so the next visit rebuilds). ``setup_steps`` run once per subject
    before the window; ``warm_visits`` visits run before it too, so every
    program the window uses is compiled or loaded from the cache in set-up.

``open``
    Requests arrive on a fixed schedule (``rate_per_s``, Poisson gaps,
    :func:`bench.labels.arrival_offsets`) from a load generator in a child
    process that imports no JAX (:mod:`bench.loadgen`), over HTTP to an
    :class:`repro.serve.http.EdgeThread` in this process. Each request is
    one ``cv`` workload whose labels are a fresh balanced split of the
    trials; latency is timed from when the request was due.

Every call into the program from the window is wrapped in a
``jax.profiler.TraceAnnotation`` named ``bench.<step>``, so a traced run
can say what the host was doing in each device idle gap.
"""

from __future__ import annotations

import contextlib
import json
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np

from bench.labels import derived_seeds, random_split

__all__ = ["ClosedLoop", "OpenHTTPLoop", "make_loop"]

_SEED_STREAM_VISITS = 1
_SEED_STREAM_WARM = 2
_SEED_STREAM_SETUP = 4
_MAX_VISITS = 1 << 16


def _annotate(name: str, on: bool):
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


class Subject:
    """One simulated subject: device features, host labels, folds, registry handle."""

    def __init__(self, x, labels, te, tr, lam, num_classes):
        self.x, self.te, self.tr, self.lam = x, te, tr, lam
        self.num_classes = num_classes
        labels = np.asarray(labels)
        if num_classes == 2:
            self.y = np.where(labels == 0, -1.0, 1.0).astype(np.float32)
        else:
            self.y = labels.astype(np.int32)
        self.handle = None

    def workload_args(self) -> dict:
        """The dataset, labels and estimator of a workload on this subject."""
        binary = self.num_classes == 2
        return dict(dataset=self.handle, y=self.y, estimator="binary" if binary else "multiclass",
                    num_classes=0 if binary else self.num_classes)


class ClosedLoop:
    """Closed loop of visits through one in-process sync ``Client``."""

    def __init__(self, traffic: dict, steps: dict, engine, subjects: list, seed: int):
        from repro.serve import Client

        self.traffic = traffic
        self.steps = steps
        self.engine = engine
        self.client = Client(engine)
        self.subjects = subjects
        self.seeds = derived_seeds(seed, _SEED_STREAM_VISITS, _MAX_VISITS)
        self.warm_seeds = derived_seeds(seed, _SEED_STREAM_WARM, 64)
        self.setup_seeds = derived_seeds(seed, _SEED_STREAM_SETUP, len(subjects))
        self.products: list = []

    def _step(self, step: dict, subj: Subject, seed: int) -> dict:
        return self.steps[step["step"]].run(self.client, self.engine, subj, step, int(seed))

    def _visit(self, index: int, seed: int, annotate: bool) -> dict:
        j = index % len(self.subjects)
        subj = self.subjects[j]
        out = {"subject": j, "seed": int(seed), "labels": 0}
        for step in self.traffic["visit"]:
            with _annotate(f"bench.{step['step']}", annotate):
                fields = self._step(step, subj, seed)
                out["labels"] += fields.pop("labels", 0)
                out.update(fields)
        return out

    # -- set-up and window ---------------------------------------------------

    def setup(self, seconds: float) -> None:
        for subj, seed in zip(self.subjects, self.setup_seeds):
            for step in self.traffic.get("setup_steps", []):
                self._step(step, subj, seed)
        for i in range(int(self.traffic.get("warm_visits", 1))):
            self._visit(i, self.warm_seeds[i], annotate=False)

    def run(self, seconds: float, annotate: bool) -> dict:
        self.products = []
        t0 = time.perf_counter()
        deadline = t0 + seconds
        i = 0
        while time.perf_counter() < deadline:
            if i >= _MAX_VISITS:
                raise RuntimeError(f"more than {_MAX_VISITS} visits in one window")
            self.products.append(self._visit(i, self.seeds[i], annotate))
            i += 1
        window = time.perf_counter() - t0
        draws = sum(p.get("n_perm", 0) for p in self.products)
        return {
            "window_s": window,
            "visits": len(self.products),
            "labels": sum(p["labels"] for p in self.products),
            "draws": draws,
            "attempted": len(self.products),
            "failed": 0,
        }


class OpenHTTPLoop:
    """Open loop of ``cv`` requests over HTTP from a JAX-free child process."""

    def __init__(self, traffic: dict, engine, subjects: list, seed: int):
        if len(subjects) != 1:
            raise ValueError("the open HTTP loop serves one subject")
        self.traffic = traffic
        self.engine = engine
        self.subject = subjects[0]
        self.seed = int(seed)
        self.edge = None
        self.child = None
        self.result = None

    def _send(self, msg: dict) -> None:
        self.child.stdin.write(json.dumps(msg) + "\n")
        self.child.stdin.flush()

    def _recv(self) -> dict:
        line = self.child.stdout.readline()
        if not line:
            raise RuntimeError(f"load generator exited (code {self.child.poll()})")
        return json.loads(line)

    def setup(self, seconds: float) -> None:
        from repro.serve import Client, Workload
        from repro.serve.http import EdgeThread

        subj = self.subject
        t = self.traffic
        subj.handle = Client(self.engine).register(subj.x, (subj.te, subj.tr), subj.lam)
        self.engine.warmup(subj.handle, tasks=("binary",), buckets=t["warm_buckets"])
        self.edge = EdgeThread(self.engine, gather_window_ms=float(t["gather_window_ms"]),
                               max_batch=int(t["max_batch"]))
        template = Workload(kind="cv", dataset=subj.handle, y=subj.y).to_dict()
        template["y"] = None
        self.child = subprocess.Popen(
            [sys.executable, str(Path(__file__).with_name("loadgen.py"))],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self._send({
            "url_port": self.edge.port,
            "template": template,
            "n": int(subj.x.shape[0]),
            "seed": self.seed,
            "rate_per_s": float(t["rate_per_s"]),
            "seconds": float(seconds),
            "connections": int(t["connections"]),
            "warm_requests": int(t["warm_requests"]),
        })
        ready = self._recv()
        if not ready.get("ready"):
            raise RuntimeError(f"load generator not ready: {ready}")

    def run(self, seconds: float, annotate: bool) -> dict:
        sample = int(self.traffic["check_requests"])
        with _annotate("bench.http_window", annotate):
            self._send({"go": True, "sample": sample})
            self.result = self._recv()
        r = self.result
        due, done = np.asarray(r["due"]), np.asarray(r["done"])
        ok = np.asarray(r["ok"], bool)
        lat = (done - due) * 1e3
        return {
            "window_s": float(max(done.max(), due.max())),
            "latencies_ms": lat.tolist(),
            "late_ms": ((np.asarray(r["sent"]) - due) * 1e3).tolist(),
            "requests": int(due.size),
            "labels": int(ok.sum()),
            "attempted": int(due.size),
            "failed": int((~ok).sum()),
        }

    def close(self) -> None:
        if self.child is not None:
            if self.child.poll() is None:
                with contextlib.suppress(BrokenPipeError, OSError):
                    self._send({"quit": True})
            try:
                self.child.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.child.kill()
                self.child.wait()
            self.child = None
        if self.edge is not None:
            self.edge.stop()
            self.edge = None

    def sampled(self) -> list:
        """(request index, labels, decision values) of the sampled requests."""
        n = int(self.subject.x.shape[0])
        out = []
        for i, values in self.result["sampled"]:
            y = random_split(self.seed, i, n)
            out.append((i, y, None if values is None else np.asarray(values, np.float64)))
        return out


def make_loop(cell, engine, subjects: list, seed: int):
    """The cell's loop (its mix's ``loop``), with the step modules a closed loop runs."""
    traffic = cell.traffic
    if traffic["loop"] == "closed":
        return ClosedLoop(traffic, cell.steps, engine, subjects, seed)
    if traffic["loop"] == "open":
        return OpenHTTPLoop(traffic, engine, subjects, seed)
    raise ValueError(f"unknown loop {traffic['loop']!r}; expected one of ['closed', 'open']")
