"""Replans of the ``torus16-chaos-online`` cell on the CPU, with the
program's planner steered to fp32 (its precision on a TPU), compared
stage by stage with the float64 reference.

``replan_inputs`` stands in for the simulation: the cell's own storm,
with the control plane's traffic estimate replaced by a sampled,
smoothed observation of the matrix in force, one replan per event
cycle.  ``compare_replans`` drives the program's own ``replan`` over
them, the N-Rank fixed point carried from each replan into the next as
the control plane carries it, and compares every replan with the
reference, which carries its own.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench_tiny import BENCH

CONFIG = "tpu-v5e-pod-torus16"
MIX = "chaos-online"
STAGES = ("w_nr", "argmin", "refine")


@pytest.fixture
def fp32_planner(monkeypatch):
    """The program's planner at fp32 where it would choose itself."""
    import repro.core.plan_fast as pf
    orig = pf._resolve_precision
    monkeypatch.setattr(pf, "_resolve_precision",
                        lambda p: "fp32" if p == "auto" else orig(p))


def load():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", MIX + ".json")) as f:
        mix = json.load(f)
    return config, mix


def replan_inputs(seed: int, count: int | None = None,
                  packets: int = 20000):
    """The (estimate, bandwidths) pairs of the cell's first measured
    storm, one per event cycle: the first ``count``, or all of them."""
    from qsbench import generator
    from qsbench.ref.grid import make_grid

    config, mix = load()
    grid = make_grid(config["fabric"], config["dims"])
    stream = generator.jobs(mix, config, grid, seed)
    next(stream)
    job = next(stream)
    channels = grid.channels()
    index = {(int(u), int(v)): i for i, (u, v) in enumerate(channels)}
    rng = np.random.default_rng([seed, 17])
    bw = np.ones(len(channels))
    cur = job["traffic"]
    est = None
    out = []
    for cyc in sorted({e.cycle for e in job["events"]}):
        for e in job["events"]:
            if e.cycle != cyc:
                continue
            if e.kind == "drift":
                cur = e.traffic
            else:
                ids = [index[tuple(lk)] for lk in e.links]
                bw = bw.copy()
                bw[ids] = 0.0 if e.kind == "fail" else 1.0
        obs = rng.multinomial(packets, cur.ravel()).reshape(cur.shape)
        obs = obs / obs.sum()
        est = obs if est is None else 0.5 * est + 0.5 * obs
        m = est.copy()
        np.fill_diagonal(m, 0.0)
        out.append((m / m.sum(), bw.copy()))
        if len(out) == count:
            break
    return grid, job["traffic"], out


def compare_replans(seed: int, count: int | None = None) -> list[dict]:
    """One row per replan: whether the N-Rank weights left 1e-3 of the
    reference's (``w_nr``), the BiDOR entries that differ (``argmin``)
    and their cost gap (``gap``), the shed pairs that differ (``shed``),
    and the entries where the shipped table differs from the reference's
    BiDOR-G refinement of the program's own BiDOR table (``refine``)."""
    from qsbench.drivers import Recorder, program_topology
    from qsbench.ref.planner import Planner, Refiner, choice_gap
    from repro.core.plan_fast import build_plan_fast
    from repro.noc import ctrl

    config, _ = load()
    sweeps = config["replan"]["greedy_sweeps"]
    grid, base, inputs = replan_inputs(seed, count)
    topo = program_topology(config)
    planner, refiner = Planner(grid), Refiner(grid)
    rec = Recorder()
    rec.new_session()
    prev = build_plan_fast(topo, base).nrank
    ref_prev = planner.plan(base)["w_final"]
    weights = []
    with rec.attached():
        for m, bw in inputs:
            _, prev = ctrl.replan(topo, m, bw, prev, warm=True,
                                  greedy_sweeps=sweeps)
            weights.append(np.asarray(prev.w_nr, np.float64))
    assert len(rec.sessions[0]) == len(inputs)
    rows = []
    for (m, bw), r, w_nr in zip(inputs, rec.sessions[0], weights):
        ref = planner.plan(m, bw=bw, w0=m.sum(1) + ref_prev)
        ref_prev = ref["w_final"]
        plan = r["plan"]
        refined = refiner.refine(m, plan["choice"], plan["unroutable"], bw,
                                 sweeps)
        unr = (np.zeros_like(plan["choice"], bool)
               if plan["unroutable"] is None else plan["unroutable"])
        ref_unr = (np.zeros_like(unr) if ref["unroutable"] is None
                   else ref["unroutable"])
        rows.append(dict(
            w_nr=float(np.abs(w_nr - ref["w_nr"]).max()
                       / np.abs(ref["w_nr"]).max()) > 1e-3,
            argmin=int((plan["choice"] != ref["choice"]).sum()),
            gap=choice_gap(ref["costs"], plan["choice"],
                           ref["unroutable"]),
            shed=int((unr != ref_unr).sum()),
            refine=int((refined != r["shipped"]).sum())))
    return rows


def first_difference(rows) -> str:
    for stage in STAGES:
        if any(r[stage] for r in rows):
            return stage
    return "none"


def summary(seed: int, rows) -> str:
    return (f"seed {seed}: {len(rows)} replans, first difference at "
            f"{first_difference(rows)}; "
            + "; ".join(f"argmin entries {r['argmin']} gap {r['gap']:.3e} "
                        f"refine {r['refine']}" for r in rows))
