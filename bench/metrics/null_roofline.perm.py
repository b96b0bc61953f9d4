"""Roofline share of the binary permutation-null program (%).

The least time the chip needs for the work of every permutation visit in
the window — per visit one observed evaluation (1 label vector) and one
null evaluation of the draws requested (not the padded bucket), each
reading the plan once (:func:`bench.work.binary_eval`) — over the device
time of the XLA module that evaluates them (the jitted ``_eval`` of the
engine's permutation path, observed and null alike). At N = 787 the null
call is compute-bound and the observed one memory-bound.
"""

import re

from bench import work

MODULE = re.compile(r"^jit__eval$")


def read(rec):
    dt, peak, sh = rec.get("device_trace"), rec.get("peak"), rec.get("shapes")
    visits, draws = rec.get("visits"), rec.get("draws")
    if not dt or not peak or not visits or not draws:
        return None
    t_dev = sum(v for k, v in dt["modules"].items() if MODULE.search(k))
    if t_dev <= 0:
        return None
    n_perm = draws // visits
    least = sum(work.least_time(*work.binary_eval(sh["n"], sh["k"], sh["m"], b), peak)[0]
                for b in (1, n_perm))
    return 100.0 * visits * least / t_dev
