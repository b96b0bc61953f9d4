"""95th-percentile latency of every request due in the traced window (ms).

Timed from when each request was due, by the load generator's clock; a
failed request stays in with the time its failure took. Read in the
``--trace 1`` run, so the engine's span tracing is on.
"""

from bench.stats import percentile


def read(rec):
    lat = rec.get("latencies_ms")
    return percentile(lat, 95) if lat else None
