"""``null_chunk`` stage time per subject visit (ms): the multi-class permutation null."""


def read(rec):
    st, visits = rec.get("stages"), rec.get("visits")
    if not st or not visits or not st["null_chunk"]["count"]:
        return None
    return 1e3 * st["null_chunk"]["sum_s"] / visits
