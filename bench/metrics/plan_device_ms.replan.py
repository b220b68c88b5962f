"""Median host time of the device planner's jitted call in a replan:
the ``plan_device`` spans (inputs to the device, the plan computed,
results back) that carry a replan's ordinal."""

import statistics


def read(run):
    ms = [s["dur"] / 1e3 for s in run.spans if s["name"] == "plan_device"
          and s["args"].get("replan") is not None]
    return statistics.median(ms) if ms else None
