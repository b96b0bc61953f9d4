"""The one traffic generator: a mix file's parameters in, a measured window out.

A mix (``bench/traffic/<mix>.json``) is data. Its ``loop`` picks one of
two drivers:

``closed``
    One in-process :class:`repro.serve.Client` (sync transport) runs
    *visits* back to back until the window closes. A visit is the mix's
    ``visit`` list of steps against one subject, cycling through the
    subjects set-up simulated: ``register`` (the dataset), ``cv``,
    ``permutation`` (with ``n_perm`` draws and a fresh seed per visit) and
    ``release`` (the engine forgets the dataset and its plans, so the next
    visit rebuilds). ``setup_steps`` run once per subject before the
    window; ``warm_visits`` visits run before it too, so every program the
    window uses is compiled or loaded from the cache in set-up.

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


class ClosedLoop:
    """Closed loop of visits through one in-process sync ``Client``."""

    def __init__(self, traffic: dict, engine, subjects: list, seed: int):
        from repro.serve import Client

        self.traffic = traffic
        self.engine = engine
        self.client = Client(engine)
        self.subjects = subjects
        self.seeds = derived_seeds(seed, _SEED_STREAM_VISITS, _MAX_VISITS)
        self.warm_seeds = derived_seeds(seed, _SEED_STREAM_WARM, 64)
        self.products: list = []

    # -- steps ---------------------------------------------------------------

    def _workload(self, step: dict, subj: Subject, seed: int):
        from repro.serve import Workload

        est = "binary" if subj.num_classes == 2 else "multiclass"
        common = dict(dataset=subj.handle, y=subj.y, estimator=est,
                      num_classes=0 if est == "binary" else subj.num_classes)
        if step["step"] == "cv":
            return Workload(kind="cv", **common)
        return Workload(kind="permutation", n_perm=int(step["n_perm"]), seed=int(seed), **common)

    def _visit(self, index: int, seed: int, annotate: bool) -> dict:
        j = index % len(self.subjects)
        subj = self.subjects[j]
        out = {"subject": j, "seed": int(seed), "labels": 0}
        for step in self.traffic["visit"]:
            kind = step["step"]
            with _annotate(f"bench.{kind}", annotate):
                if kind == "register":
                    subj.handle = self.client.register(subj.x, (subj.te, subj.tr), subj.lam)
                elif kind == "release":
                    self.engine.release(subj.handle)
                    subj.handle = None
                elif kind == "cv":
                    resp = self.client.submit(self._workload(step, subj, seed))
                    out["cv"] = np.asarray(resp.values)
                    out["labels"] += 1
                elif kind == "permutation":
                    resp = self.client.submit(self._workload(step, subj, seed))
                    out["null"] = np.asarray(resp.null)
                    out["observed"] = float(resp.observed)
                    out["n_perm"] = int(step["n_perm"])
                    out["labels"] += out["null"].size + 1
                else:
                    raise ValueError(f"unknown step {kind!r} in the traffic mix")
        return out

    # -- set-up and window ---------------------------------------------------

    def setup(self, seconds: float) -> None:
        for subj in self.subjects:
            for step in self.traffic.get("setup_steps", []):
                if step["step"] != "register":
                    raise ValueError(f"set-up step {step['step']!r} is not supported")
                subj.handle = self.client.register(subj.x, (subj.te, subj.tr), subj.lam)
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


def make_loop(traffic: dict, engine, subjects: list, seed: int):
    loops = {"closed": ClosedLoop, "open": OpenHTTPLoop}
    if traffic["loop"] not in loops:
        raise ValueError(f"unknown loop {traffic['loop']!r}; expected one of {sorted(loops)}")
    return loops[traffic["loop"]](traffic, engine, subjects, seed)
