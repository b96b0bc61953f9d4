"""Interpreter garbage-collection pause time per second of the window (ms/s).

The tracer's ``gc`` stage, observed from ``gc.callbacks`` on any thread
while tracing is on; 0.0 when the window had no pause, None where the
program has no such stage.
"""


def read(rec):
    st, window = rec.get("stages"), rec.get("window_s")
    pauses = (st or {}).get("gc")
    if pauses is None or not window:
        return None
    return 1e3 * pauses["sum_s"] / window
