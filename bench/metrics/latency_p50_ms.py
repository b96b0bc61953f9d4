"""Median latency of every request due in the window, timed from when it was due."""

from bench.stats import percentile


def read(rec):
    lat = rec.get("latencies_ms")
    return percentile(lat, 50) if lat else None
