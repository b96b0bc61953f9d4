"""``null_chunk`` stage time per 1000 permutation draws completed (ms)."""


def read(rec):
    st, draws = rec.get("stages"), rec.get("draws")
    if not st or not draws or not st["null_chunk"]["count"]:
        return None
    return 1e6 * st["null_chunk"]["sum_s"] / draws
