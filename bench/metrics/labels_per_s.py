"""Permutation label vectors (null draws + observed) completed per second of the window."""


def read(rec):
    if not rec.get("draws"):
        return None
    return rec["labels"] / rec["window_s"]
