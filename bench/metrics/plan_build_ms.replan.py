"""Median host time of the device planner inside a replan: the
``build_plan_fast`` spans that lie within a ``replan`` span."""

import statistics


def inside(spans, outer):
    out = [s for s in spans if s["name"] == outer]
    return lambda s: any(o["ts"] <= s["ts"] and s["ts"] + s["dur"]
                         <= o["ts"] + o["dur"] for o in out)


def read(run):
    within = inside(run.spans, "replan")
    ms = [s["dur"] / 1e3 for s in run.spans
          if s["name"] == "build_plan_fast" and within(s)]
    return statistics.median(ms) if ms else None
