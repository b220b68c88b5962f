"""Median host time of the deadlock certifier on replanned tables: the
``certify`` spans labelled ``replan``."""

import statistics


def read(run):
    ms = [s["dur"] / 1e3 for s in run.spans if s["name"] == "certify"
          and s["args"].get("label") == "replan"]
    return statistics.median(ms) if ms else None
