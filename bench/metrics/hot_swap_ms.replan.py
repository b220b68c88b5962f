"""Median host time of a replan's hot swap: the program's ``hot_swap``
spans (shed guard, admission control, the tables retargeted)."""

import statistics


def read(run):
    ms = [s["dur"] / 1e3 for s in run.spans if s["name"] == "hot_swap"]
    return statistics.median(ms) if ms else None
