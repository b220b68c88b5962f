"""Median host time of a replan, from the start of the control plane's
``replan`` span to the new table hot-swapped, over every replan of the
window."""

import numpy as np


def read(run):
    ms = [s["dur"] / 1e3 for s in run.spans if s["name"] == "replan"]
    return float(np.percentile(ms, 50)) if ms else None
