"""HTTP edge time per request: the engine tracer's ``decode`` + ``encode`` stages (ms).

``encode`` sums the response assembly span and the wire JSON encode the
edge records beside it; both are divided by the requests in the window.
"""


def read(rec):
    st, n = rec.get("stages"), rec.get("requests")
    if not st or not n or not st["decode"]["count"]:
        return None
    return 1e3 * (st["decode"]["sum_s"] + st["encode"]["sum_s"]) / n
