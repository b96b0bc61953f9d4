"""Roofline share of the ``gram`` Pallas kernel in the plan builds (%).

The least time for the centred Gram of every subject visit in the window
(2·N²·P operations, X read once and G written once;
:func:`bench.work.gram`), over the device time of the kernel's
operations. At N = 787, P = 1900 it is compute-bound; the peak is the
chip's bfloat16 rate, and float32 at ``HIGHEST`` takes six bfloat16
passes, so the share cannot pass about 1/6 on a v5e.
"""

import re

from bench import work

OP = re.compile(r"^jit_gram/.*tpu_custom_call")


def read(rec):
    dt, peak, sh, visits = rec.get("device_trace"), rec.get("peak"), rec.get("shapes"), rec.get("visits")
    if not dt or not peak or not visits:
        return None
    t_dev = sum(v for k, v in dt["ops"].items() if OP.search(k))
    if t_dev <= 0:
        return None
    least, _ = work.least_time(*work.gram(sh["n"], sh["p"]), peak)
    return 100.0 * visits * least / t_dev
