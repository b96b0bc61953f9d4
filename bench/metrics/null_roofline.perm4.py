"""Roofline share of the mesh permutation-null program (%), against the
host's aggregate peak.

The least time one chip needs for the null of every permutation visit in
the window — the draws requested (not the padded bucket), reading the plan
once (:func:`bench.work.binary_eval`) — over the device time of the XLA
module that evaluates them on the mesh (``jit__mesh_null``: pad, sharded
eval, all-gather, slice), summed over the chips. The work counted is the
same whatever implements it, so the share is of the host's four chips
together. None where no such module ran, as on a program without it.
"""

import re

from bench import work

MODULE = re.compile(r"^jit__mesh_null$")


def read(rec):
    dt, peak, sh = rec.get("device_trace"), rec.get("peak"), rec.get("shapes")
    visits, draws = rec.get("visits"), rec.get("draws")
    if not dt or not peak or not visits or not draws:
        return None
    t_dev = sum(v for k, v in dt["modules"].items() if MODULE.search(k))
    if t_dev <= 0:
        return None
    n_perm = draws // visits
    least = work.least_time(*work.binary_eval(sh["n"], sh["k"], sh["m"], n_perm), peak)[0]
    return 100.0 * visits * least / t_dev
