"""Replay whole ``torus16-chaos-online`` sessions on the CPU with the
program's planner steered to fp32, and compare every replan with the
float64 reference, the simulation and the control plane's own traffic
estimates in the loop.

Each seed runs the first measured session of its job stream through the
harness's own driver (``run_controlled``), and prints one JSON line: the
replans, the widest N-Rank weight error relative to the reference, the
BiDOR entries that differ and their widest cost gap, the shed pairs that
differ, and the entries where the shipped table differs from the
reference's BiDOR-G refinement of the program's own BiDOR table.  The
reference carries its own N-Rank fixed point from replan to replan, as
the program carries its.

    JAX_PLATFORMS=cpu PYTHONPATH=src python tests/bench/replay_fp32_sessions.py \\
        --seeds 158735332,1,2
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

from bench_replans import load  # noqa: E402  (puts bench/ on the path)


def replay(seed: int, workdir: str) -> dict:
    from qsbench import drivers, generator
    from qsbench.ref.grid import make_grid
    from qsbench.ref.planner import Planner, Refiner, choice_gap
    from repro.noc import ctrl

    config, mix = load()
    sweeps = config["replan"]["greedy_sweeps"]
    grid = make_grid(config["fabric"], config["dims"])
    svc = drivers.Service(config, mix, workdir,
                          drivers.SpanLog(keep=("replan",)))
    stream = generator.jobs(mix, config, grid, seed)
    next(stream)
    job = next(stream)
    weights = []
    orig = ctrl.replan

    def replan(*a, **kw):
        table, nr = orig(*a, **kw)
        weights.append(np.asarray(nr.w_nr, np.float64))
        return table, nr

    ctrl.replan = replan
    try:
        t0 = time.perf_counter()
        out = svc.run(job)
        wall = time.perf_counter() - t0
    finally:
        ctrl.replan = orig
    planner, refiner = Planner(grid), Refiner(grid)
    prev, row = None, dict(seed=seed, wall_s=round(wall, 1),
                           replans=len(out["replans"]), w_nr_rel=0.0,
                           argmin_entries=0, gap=0.0, shed=0, refine=0)
    reps = [s for s in out["stages"] if s["kind"] == "replan"]
    for rec in out["stages"]:
        if rec["kind"] == "seed":
            ref = planner.plan(rec["traffic"])
            row["gap"] = choice_gap(ref["costs"], rec["plan"]["choice"])
            prev = ref["w_final"]
    for rec, w_nr in zip(reps, weights):
        ref = planner.plan(rec["traffic"], bw=rec["bw"],
                           w0=rec["traffic"].sum(1) + prev)
        prev = ref["w_final"]
        plan = rec["plan"]
        row["w_nr_rel"] = max(row["w_nr_rel"], float(
            np.abs(w_nr - ref["w_nr"]).max() / np.abs(ref["w_nr"]).max()))
        row["argmin_entries"] += int((plan["choice"] != ref["choice"]).sum())
        row["gap"] = max(row["gap"], choice_gap(ref["costs"], plan["choice"],
                                                ref["unroutable"]))
        a = plan["unroutable"] if plan["unroutable"] is not None else False
        b = ref["unroutable"] if ref["unroutable"] is not None else False
        row["shed"] += int(np.sum(np.asarray(a) != np.asarray(b)))
        refined = refiner.refine(rec["traffic"], plan["choice"],
                                 plan["unroutable"], rec["bw"], sweeps)
        row["refine"] += int((refined != rec["shipped"]).sum())
    row["compared"] = len(weights)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds of the job stream")
    args = ap.parse_args(argv)
    import repro.core.plan_fast as pf
    orig = pf._resolve_precision
    pf._resolve_precision = lambda p: "fp32" if p == "auto" else orig(p)
    with tempfile.TemporaryDirectory() as tmp:
        for s in args.seeds.split(","):
            row = replay(int(s), os.path.join(tmp, "run"))
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
