"""BiDOR-G's host time per pair it sweeps: the median over replans of a
``greedy_refine`` span's duration over its ``pairs`` count."""

import statistics


def read(run):
    us = [s["dur"] / s["args"]["pairs"] for s in run.spans
          if s["name"] == "greedy_refine" and s["args"].get("pairs")]
    return statistics.median(us) if us else None
