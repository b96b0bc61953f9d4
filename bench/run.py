#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration, a traffic mix and its
limits. One run is one process: it simulates the cell's subjects on the
device from ``--seed``, starts the served path and warms every shape the
mix uses (set-up), measures for ``--seconds`` (the window), then frees the
program's state and checks a sample of what the window produced against
the float64 refit (``correct``). With ``--trace 0`` the metrics are the
cell's end-to-end metrics; with ``--trace 1`` the window also runs under
the profiler and the engine's span tracing, and the metrics are the
cell's per-layer ones, with the device's busy time and a breakdown.

The last lines of standard error, and the last key of the result, are
the numbers compared beside their limits. The last line of standard
output is the result, one JSON object. A run that finds no TPU, or fewer
chips than the cell asks for, exits 3 and prints no result. JAX's
persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when set,
else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import numpy as np  # noqa: E402
from repro.serve import CVEngine, EngineConfig  # noqa: E402  (the system under test)

from bench import cells, check, loops, trace_reduce  # noqa: E402
from bench.labels import derived_seeds  # noqa: E402

_SEED_STREAM_SUBJECTS = 3
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def tpu_devices(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0].platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices


def setup_compilation_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``; every
    program is cached, so only a checkout's first run of a cell compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peak_row(kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())["kinds"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def kfold(n: int, k: int, seed: int):
    """Test (K, m) and train (K, N − m) indices; m = N // K, leftovers train only."""
    m = n // k
    te = np.random.default_rng(seed).permutation(n)[:k * m].reshape(k, m).astype(np.int32)
    full = np.arange(n)
    tr = np.stack([np.setdiff1d(full, row) for row in te]).astype(np.int32)
    return te, tr


def make_subjects(cell, seed: int) -> list:
    """The mix's subjects, each made by the configuration's data generator from its
    own seed; ``x`` is (…, N, P), with any grid axes in front."""
    cfg = cell.config
    count = int(cell.traffic["subjects"])
    out = []
    for s in derived_seeds(seed, _SEED_STREAM_SUBJECTS, count):
        x, y = cell.generator.simulate(int(s), cfg["data"], int(cfg["num_classes"]))
        te, tr = kfold(int(cfg["data"]["n_trials"]), int(cfg["folds"]), int(s))
        out.append(loops.Subject(x, np.asarray(y), te, tr, float(cfg["lam"]),
                                 int(cfg["num_classes"])))
    jax.block_until_ready([s.x for s in out])
    return out


def make_engine(cell, precision=None):
    opts = dict(cell.config["engine"])
    if precision is not None:
        opts["precision"] = precision
    return CVEngine(EngineConfig(**opts))


class _CompileCounter:
    """Programs JAX makes (compiled or loaded from the cache) while active."""

    def __init__(self):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self.active and event == _COMPILE_EVENT:
            self.count += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def _stage_totals(engine) -> dict:
    hist = engine.metrics.get("stage_latency_seconds")
    from repro.serve.trace import STAGES

    return {s: hist.snapshot(stage=s) for s in STAGES}


def _stage_delta(before: dict, after: dict) -> dict:
    return {s: {"count": after[s]["count"] - before[s]["count"],
                "sum_s": after[s]["sum"] - before[s]["sum"]} for s in after}


def run_cell(cell, seed: int, seconds: float, trace: bool, *, devices=None,
             precision=None, t_start=None, keep=None) -> dict:
    """One run of ``cell``: set-up, window, check. Returns the result object.

    ``devices`` are the chips the run uses (all of JAX's devices when None);
    ``precision`` overrides the engine's precision (the control); ``keep``,
    a dict, receives the subjects, the window's record and its products.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    devices = devices or jax.devices()[:cell.chips]
    cfg = cell.config
    counter = _CompileCounter()
    engine = make_engine(cell, precision)
    subjects = make_subjects(cell, seed)
    loop = loops.make_loop(cell, engine, subjects, seed)
    try:
        loop.setup(seconds)
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s:.3f}s; window {seconds}s; trace {int(trace)}")
        trace_dir = None
        if trace:
            engine.enable_tracing(ring=64)
            stages0 = _stage_totals(engine)
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        counter.active = True
        rec = loop.run(seconds, annotate=trace)
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
            rec["stages"] = _stage_delta(stages0, _stage_totals(engine))
        memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    finally:
        counter.close()
        if hasattr(loop, "close"):
            loop.close()
    products = getattr(loop, "products", None)
    sampled = loop.sampled() if hasattr(loop, "sampled") else None
    for subj in subjects:
        subj.x64 = np.asarray(subj.x, np.float64)
        subj.x = None
    del loop, engine
    gc.collect()

    rec.update(setup_s=setup_s, window_compiles=counter.count, shapes={
        "n": int(cfg["data"]["n_trials"]), "p": int(subjects[0].x64.shape[-1]),
        "k": int(cfg["folds"]), "m": int(cfg["data"]["n_trials"]) // int(cfg["folds"]),
        "num_classes": int(cfg["num_classes"]), "grid": list(subjects[0].x64.shape[:-2])})
    breakdown = None
    if trace:
        red = trace_reduce.reduce_trace(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec["device_trace"] = red
        rec["peak"] = peak_row(devices[0].device_kind) if devices[0].platform == "tpu" else None
        breakdown = {"device_ops": [[k, v] for k, v in list(red["ops"].items())[:10]],
                     "idle_gaps": [[k, v] for k, v in red["idle_gaps"][:10]]}

    if keep is not None:
        keep.update(subjects=subjects, products=products, sampled=sampled, rec=rec)
    t_check = time.perf_counter()
    if products is not None:
        numbers = check.check_closed(cell, subjects, products, seed)
    else:
        numbers = check.check_open(subjects[0], sampled)
    numbers["failed"] = rec["failed"]
    correct, rows = check.verdict(numbers, cell.limits)
    log(f"reference check {time.perf_counter() - t_check:.3f}s; "
        f"programs made in the window: {counter.count}")

    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = cell.readers[m["name"]].read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": int(memory_peak)}
    if trace:
        device.update(busy_s=rec["device_trace"]["busy_s"],
                      window_s=rec["device_trace"]["window_s"])
    result = {"correct": bool(correct), "attempted": int(rec["attempted"]),
              "failed": int(rec["failed"]), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window_compiles"] = counter.count
    result["checks"] = {name: {"value": value, "limit": limit} for name, value, limit in rows}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    try:
        devices = tpu_devices(cell.chips)
    except NoChip as e:
        log(f"refusing to run: {e}")
        return 3
    log(f"device {devices[0].device_kind} x{len(devices)}; "
        f"compilation cache {setup_compilation_cache()}")
    peak_row(devices[0].device_kind)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices=devices[:cell.chips], t_start=T_START)
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
