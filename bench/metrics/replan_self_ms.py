"""Median self time of the control plane in a replan: the ``replan``
span less the device planner's ``build_plan_fast`` span and the
certifier's ``certify`` span labelled ``replan`` inside it.  What is
left is BiDOR-G's greedy refinement, admission control and the hot-swap
enqueue."""

import statistics


def child(s):
    return s["name"] == "build_plan_fast" or (
        s["name"] == "certify" and s["args"].get("label") == "replan")


def read(run):
    kids = [s for s in run.spans if child(s)]
    ms = []
    for r in run.spans:
        if r["name"] != "replan":
            continue
        lo, hi = r["ts"], r["ts"] + r["dur"]
        inner = sum(k["dur"] for k in kids
                    if lo <= k["ts"] and k["ts"] + k["dur"] <= hi)
        ms.append((r["dur"] - inner) / 1e3)
    return statistics.median(ms) if ms else None
