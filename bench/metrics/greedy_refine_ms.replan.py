"""Median host time of BiDOR-G in a replan: the program's
``greedy_refine`` spans."""

import statistics


def read(run):
    ms = [s["dur"] / 1e3 for s in run.spans if s["name"] == "greedy_refine"]
    return statistics.median(ms) if ms else None
