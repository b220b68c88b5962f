"""BiDOR-G's host time per route hop it weighs: the median over replans
of a ``greedy_refine`` span's duration over its ``hops`` count (the
route hops of every order, summed over the pairs it sweeps), in ns.
Comparable between fabrics whose routes differ in length."""

import statistics


def read(run):
    ns = [s["dur"] * 1e3 / s["args"]["hops"] for s in run.spans
          if s["name"] == "greedy_refine" and s["args"].get("hops")]
    return statistics.median(ns) if ns else None
