"""Reduce a profiler trace (``.xplane.pb``) to device busy time, op and module times, gaps.

Read with ``jax.profiler.ProfileData`` alone. Device operations are the
events of the ``XLA Ops`` lines of the ``/device:TPU:<i>`` planes, named
``<module>/<op>`` (the jitted program they run in, its numeric suffix
dropped, and the HLO op's name with its custom-call target), and module
events those of their ``XLA Modules`` lines. A
trace with no device plane — one recorded on the CPU — takes as device
operations the host events that carry an ``hlo_op`` statistic, so the
reduction can be checked without a chip.

For each device: busy time is the union of its operations' intervals
inside the window; idle gaps are the holes in that union. Each gap is
attributed to what the host was doing at its midpoint: the innermost
``bench.*`` annotation open then (the benchmark's spans around each call
into the program), joined with the innermost other host event, if any.
"""

from __future__ import annotations

import bisect
import glob
import heapq
import re
from collections import defaultdict
from pathlib import Path

__all__ = ["find_xplane", "reduce_trace"]

_COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
                         r"|allreduce|allgather|send|recv", re.IGNORECASE)
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def find_xplane(log_dir) -> Path:
    files = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(files[-1])


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _op_label(name: str) -> str:
    """``%fusion.3 = f32[..] fusion(..), calls=..`` → ``%fusion.3``, plus the
    custom-call target when there is one."""
    short = name.split(" = ", 1)[0]
    m = _TARGET.search(name)
    return f"{short} ({m.group(1)})" if m else short


def _in_modules(ops, modules):
    """Prefix each op with the (suffix-stripped) name of the module it runs in."""
    mods = sorted(modules, key=lambda e: e[1])
    starts = [a for _, a, _ in mods]
    out = []
    for name, a, b in ops:
        i = bisect.bisect_right(starts, a) - 1
        mod = _MODULE_SUFFIX.sub("", mods[i][0]) if i >= 0 and a < mods[i][2] else "?"
        out.append((f"{mod}/{_op_label(name)}", a, b))
    return out


def _device_events(planes):
    """{device name: {"ops": [...], "modules": [...]}} of (name, start_ns, end_ns)."""
    devices = {}
    for plane in planes:
        if not re.match(r"/device:TPU:\d+$", plane.name):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
            elif line.name == "XLA Modules":
                modules += [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
        devices[plane.name] = {"ops": _in_modules(ops, modules), "modules": modules}
    if devices:
        return devices
    ops, modules = [], {}
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                st = _stats(e)
                if "hlo_op" in st and e.duration_ns > 0:
                    ops.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
                    mod = str(st.get("hlo_module", ""))
                    a, b = modules.get(mod, (e.start_ns, e.start_ns))
                    modules[mod] = (min(a, e.start_ns), max(b, e.start_ns + e.duration_ns))
    mods = [(name, a, b) for name, (a, b) in modules.items()]
    return {"host-cpu": {"ops": ops, "modules": mods}} if ops else {}


def _host_events(planes):
    """(bench annotations, other host events) as (name, start_ns, end_ns) lists."""
    bench, other = [], []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns <= 0:
                    continue
                item = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                (bench if e.name.startswith("bench.") else other).append(item)
    return bench, other


def _innermost_at(events, times):
    """For each time (ascending), the open event with the latest start, or None.

    A sweep over events sorted by start, with a heap of the open ones
    keyed by start; events that ended before the query time are dropped.
    """
    events = sorted(events, key=lambda e: e[1])
    heap, out, j = [], [], 0
    for t in times:
        while j < len(events) and events[j][1] <= t:
            name, a, b = events[j]
            heapq.heappush(heap, (-a, b, name))
            j += 1
        while heap and heap[0][1] < t:
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else None)
    return out


def reduce_trace(path, window=None) -> dict:
    """Reduce one trace file.

    ``window`` is (start_ns, end_ns) on the trace's clock; by default the
    span of the ``bench.*`` annotations, or of the device operations when
    there are none. Returns seconds: ``busy_s`` and ``window_s`` (busy
    averaged over devices), ``ops`` and ``modules`` (device time by name,
    summed over devices), ``collective_s``, ``idle_gaps`` (idle time by
    host activity on the first device) and ``devices``.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = list(data.planes)
    devices = _device_events(planes)
    if not devices:
        raise ValueError(f"{path}: no device operations in the trace")
    bench, other = _host_events(planes)
    if window is None:
        spans = bench or [op for d in devices.values() for op in d["ops"]]
        window = (min(a for _, a, _ in spans), max(b for _, _, b in spans))
    lo, hi = window
    ops_time = defaultdict(float)
    mod_time = defaultdict(float)
    busy_total = collective = 0.0
    gaps = defaultdict(float)
    for idx, (dev, ev) in enumerate(sorted(devices.items())):
        clipped = [(n, a, b) for n, a, b in ((n, max(a, lo), min(b, hi)) for n, a, b in ev["ops"])
                   if b > a]
        for name, a, b in clipped:
            ops_time[name] += (b - a) * 1e-9
            if _COLLECTIVE.search(name):
                collective += (b - a) * 1e-9
        for name, a, b in ev["modules"]:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                mod_time[_MODULE_SUFFIX.sub("", name)] += (b - a) * 1e-9
        merged = _merge([(a, b) for _, a, b in clipped])
        busy_total += sum(b - a for a, b in merged) * 1e-9
        if idx == 0:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            holes = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
            mids = [0.5 * (a + b) for a, b in holes]
            for (a, b), span, host in zip(holes, _innermost_at(bench, mids),
                                          _innermost_at(other, mids)):
                label = span or "outside bench spans"
                if host is not None:
                    label = f"{label} / {host}"
                gaps[label] += (b - a) * 1e-9
    n_dev = len(devices)
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])  # noqa: E731
    return {
        "devices": n_dev,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / n_dev,
        "ops": dict(top(ops_time)),
        "modules": dict(top(mod_time)),
        "collective_s": collective,
        "idle_gaps": top(gaps),
    }
