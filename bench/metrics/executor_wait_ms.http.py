"""Mean wait per request for the async server's one engine thread (ms).

The tracer's ``executor_wait`` stage at top level: the ``run_workloads``
hop and the wire-encode hop. The decode hop's wait is a child of
``decode`` and stays inside ``edge_ms.http``. None where the program has
no such stage.
"""


def read(rec):
    st, n = rec.get("stages"), rec.get("requests")
    wait = (st or {}).get("executor_wait")
    if not wait or not n or not wait["count"]:
        return None
    return 1e3 * wait["sum_s"] / n
