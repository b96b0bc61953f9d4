"""Mean ``batch_wait`` stage per request (ms): submit to dequeue in the gather window."""


def read(rec):
    st = rec.get("stages")
    if not st or not st["batch_wait"]["count"]:
        return None
    return 1e3 * st["batch_wait"]["sum_s"] / st["batch_wait"]["count"]
