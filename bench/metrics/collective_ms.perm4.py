"""Device time in collectives per analysis per chip (ms).

The trace's collective operations (all-gather, all-reduce and the like,
``device_trace.collective_s``, summed over the chips) over the window's
permutation visits and the chips that ran them. In the four-chip
permutation cell that is the all-gather of the sharded null; the
feature-sharded Gram's all-reduce runs in set-up. None where the trace
has no collective, as on one chip.
"""


def read(rec):
    dt, visits = rec.get("device_trace"), rec.get("visits")
    if not dt or not visits or dt.get("collective_s", 0.0) <= 0:
        return None
    return 1e3 * dt["collective_s"] / visits / dt["devices"]
