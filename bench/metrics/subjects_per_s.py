"""Whole single-subject analyses (visits) completed per second of the window."""


def read(rec):
    if not rec.get("visits"):
        return None
    return rec["visits"] / rec["window_s"]
