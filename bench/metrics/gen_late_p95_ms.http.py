"""95th percentile of how late the load generator sent each request (send − due, ms)."""

from bench.stats import percentile


def read(rec):
    late = rec.get("late_ms")
    return percentile(late, 95) if late else None
