"""The one-chip cells read what they read before the reference fabric
took any number of dimensions.

At a reduced size (the mesh cell on an 8x8 mesh, the torus cell on a
6x6 torus with a shortened storm), each cell's first job stream, the
reference planner's tables, the reference simulator's lanes and, for
the control-plane cell, the reference replay of a session with every
replan's planned and refined table hash to the values frozen from the
2-D-only harness.  Run as a script, it prints the digests of the
``bench/`` it finds beside it:

    python tests/bench/test_bench_readings_frozen.py
"""

from __future__ import annotations

import hashlib
import json
import os

import numpy as np
import pytest

from bench_tiny import BENCH

SEED = 2**31 + 7
REDUCED = {
    "mesh32-bidor-transpose": dict(
        config=dict(dims=[8, 8], cycles=120, warmup=40, chunk=40)),
    "torus16-chaos-online": dict(
        config=dict(dims=[6, 6], cycles=600, warmup=100, epoch=100),
        storm=dict(start=50, horizon=500, flap_period=50)),
}

# sha256 (first 16 hex digits) of each reading, from the harness as it
# stood when its reference fabric took 2-D only
FROZEN = {
    "mesh32-bidor-transpose": {
        "jobs": "096d031c920bd415",
        "seed_plan": "803d7086fbb517d8",
        "lanes": "ece9e93ef8160736",
    },
    "torus16-chaos-online": {
        "jobs": "b4601f595c5464d2",
        "seed_plan": "34d4cbc65218d0bc",
        "replay": "355133b069f3fca5",
        "replans": "3641a60d81805589",
        "replan_count": "13",
    },
}


def _digest(x) -> str:
    h = hashlib.sha256()

    def feed(v):
        if isinstance(v, dict):
            for k in sorted(v):
                h.update(repr(k).encode())
                feed(v[k])
        elif isinstance(v, (list, tuple)):
            h.update(f"[{len(v)}".encode())
            for e in v:
                feed(e)
        elif isinstance(v, np.ndarray) or hasattr(v, "dtype"):
            a = np.ascontiguousarray(np.asarray(v))
            h.update(str((a.dtype.str, a.shape)).encode() + a.tobytes())
        elif hasattr(v, "kind") and hasattr(v, "cycle"):
            feed(dict(kind=v.kind, cycle=v.cycle, links=list(v.links),
                      traffic=v.traffic))
        else:
            h.update(repr(v).encode())
    feed(x)
    return h.hexdigest()[:16]


def _cell(name: str):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        w = [x for x in json.load(f)["workloads"] if x["name"] == name][0]
    with open(os.path.join(BENCH, "configs", w["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        mix = json.load(f)
    config.update(REDUCED[name]["config"])
    if "storm" in REDUCED[name]:
        mix["storm"].update(REDUCED[name]["storm"])
    return config, mix


def digests(name: str) -> dict:
    """Digests of one cell's readings at the reduced size."""
    from qsbench import generator
    from qsbench.check import points, sim_params
    from qsbench.ref import control as rctrl
    from qsbench.ref import sim as rsim
    from qsbench.ref.grid import make_grid
    from qsbench.ref.planner import Planner, Refiner

    config, mix = _cell(name)
    grid = make_grid(config["fabric"], config["dims"])
    stream = generator.jobs(mix, config, grid, SEED)
    jobs = [next(stream) for _ in range(3)]
    out = {"jobs": _digest(jobs)}
    planner = Planner(grid)
    base = jobs[0]["traffic"]
    seed_plan = planner.plan(base)
    out["seed_plan"] = _digest(seed_plan)
    if mix["service"] == "campaign":
        sim = sim_params(config, mix["algo"])
        lanes = rsim.run_campaign_lanes(grid, base, seed_plan["choice"], sim,
                                        points(jobs[1]), config["chunk"])
        out["lanes"] = _digest(lanes)
        return out
    sim = sim_params(config, config["algo"])
    rp = config["replan"]
    job = jobs[1]
    replay = rctrl.replay(grid, base, sim, rp, config["epoch"],
                          job["events"], points(job), seed_plan["choice"],
                          [seed_plan["choice"]] * 64)
    out["replay"] = _digest(replay)
    refiner, prev, plans = Refiner(grid), seed_plan["w_final"], []
    for inp in replay["inputs"]:
        ref = planner.plan(inp["traffic"], bw=inp["bw"],
                           w0=inp["traffic"].sum(1) + prev)
        prev = ref["w_final"]
        refined = refiner.refine(inp["traffic"], ref["choice"],
                                 ref["unroutable"], inp["bw"],
                                 rp["greedy_sweeps"])
        plans.append(dict(plan=ref, refined=refined,
                          unroutable=rctrl.unroutable(
                              grid, grid.channels(), inp["bw"])))
    out["replans"] = _digest(plans)
    out["replan_count"] = str(len(plans))
    return out


@pytest.mark.parametrize("name", sorted(FROZEN))
def test_one_chip_cells_read_as_the_2d_harness(name):
    assert digests(name) == FROZEN[name]


if __name__ == "__main__":
    print(json.dumps({n: digests(n) for n in sorted(FROZEN)}, indent=1))
