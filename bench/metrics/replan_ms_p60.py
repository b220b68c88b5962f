"""60th percentile of the host time of a replan (see replan_ms_p50): the
highest round percentile that a window of this cell holds ten replans
beyond (26 replans in 51 s on one TPU v5e host: two sessions of 13)."""

import numpy as np


def read(run):
    ms = [s["dur"] / 1e3 for s in run.spans if s["name"] == "replan"]
    return float(np.percentile(ms, 60)) if ms else None
