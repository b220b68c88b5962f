"""Share of its roofline that the planner's possibility kernel reaches:
the least time of the passes it ran in the traced window (operations and
bytes counted from the shapes by ``qsbench.roofline``, against the
chip's published peaks) over its device time."""

import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from qsbench.roofline import least_time, possibility_work  # noqa: E402

KERNEL = "possibility_v_pallas"


def read(run):
    if run.trace is None or not run.peaks:
        return None
    hits = {k: v for k, v in run.trace["ops"].items() if KERNEL in k}
    calls = sum(run.trace["op_counts"].get(k, 0) for k in hits)
    dev_s = sum(hits.values()) * run.trace["devices"]
    if not calls or dev_s <= 0:
        return None
    n = math.prod(run.config["dims"])
    ops, nbytes = possibility_work(n, n)
    return 100.0 * calls * least_time(ops, nbytes, run.peaks) / dev_s
