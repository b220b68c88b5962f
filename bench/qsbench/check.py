"""The comparison that decides ``correct``.

Each number compared has a limit; a run is correct when every number is
at or under its limit.  The limits and the readings they were set from
are in ``PERF.md``.

* ``jobs_failed`` — jobs that raised or did not complete.
* ``lanes_unsound`` — lanes, over every job of the window, whose flits
  are not conserved (injected ≠ ejected + in flight).
* ``lanes_differing`` — lanes of the sampled jobs whose statistics are
  not exactly those of the reference simulator.
* ``argmin_gap`` — the widest relative excess of a BiDOR table's route
  cost over the cheapest route, both priced by the float64 reference
  planner (seed plans and every replan of the sampled session).
* ``shed_pairs_differing`` — pairs whose unroutable flag differs from the
  reference's after a fault.
* ``refine_entries_differing`` — entries where the shipped table differs
  from the reference's BiDOR-G refinement of the program's own BiDOR
  table (refinement and certification, with the planner's near-ties
  taken as the program broke them).
* ``schedule_differing`` — replans of the sampled session whose cycle,
  trigger, drift distance, shed verdict or inputs differ from the
  reference control plane's, plus replans one side made and the other
  did not.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .ref import control as rctrl
from .ref import sim as rsim
from .ref.planner import Planner, Refiner, choice_gap

# the limit of each number; see PERF.md for the readings behind them
LIMITS = {
    "jobs_failed": 0,
    "lanes_unsound": 0,
    "lanes_differing": 0,
    "argmin_gap": 1e-3,
    "shed_pairs_differing": 0,
    "refine_entries_differing": 0,
    "schedule_differing": 0,
}

STAT_FIELDS = ("throughput", "offered", "avg_latency", "max_latency",
               "node_load", "lcv", "reorder_value", "ejected_flits",
               "injected_flits", "in_flight_flits", "meas_cycles",
               "saturated", "p50_latency", "p90_latency", "p99_latency",
               "link_load_max")


def sim_params(config: dict, algo: str) -> dict:
    return dict(algo=algo, num_vcs=config["num_vcs"],
                buf_per_vc=config["buf_per_vc"],
                packet_len=config["packet_len"],
                src_queue_pkts=config["src_queue_pkts"],
                cycles=config["cycles"], warmup=config["warmup"],
                drain=config["drain"], lat_bins=96, lat_bin_width=8)


def lanes_differing(results, ref_stats) -> int:
    """Lanes whose statistics differ in any field (exact comparison:
    every field is an integer count or the same arithmetic on them)."""
    if len(results) != len(ref_stats):
        return max(len(results), len(ref_stats))
    bad = 0
    for r, s in zip(results, ref_stats):
        a = dataclasses.asdict(r) if dataclasses.is_dataclass(r) else r
        if not all(np.array_equal(a[f], s[f]) for f in STAT_FIELDS):
            bad += 1
    return bad


def lanes_unsound(outputs) -> int:
    return sum(1 for o in outputs for r in o["results"]
               if r.injected_flits != r.ejected_flits + r.in_flight_flits)


def jobs_failed(outputs) -> int:
    return sum(1 for o in outputs if not o["complete"]
               or len(o["results"]) != o["lanes"])


def points(job) -> list:
    return [(float(r), int(s)) for r in job["rates"] for s in job["seeds"]]


# lanes of the sampled campaign job that the reference simulates
MAX_LANES = 4


def sample_lanes(count: int, chips: int, seed: int) -> list:
    """Indices of the lanes that the reference simulates again: every
    lane up to :data:`MAX_LANES`, else that many drawn from ``seed``.
    Where the lanes divide over the chips, the program shards them in
    contiguous blocks (chip k holds lanes k·count/chips to
    (k+1)·count/chips − 1), and the draw takes as many from each block,
    so that a fault in any one chip's shard is always sampled."""
    rng = np.random.default_rng([int(seed), 0x1A4E])
    if count <= MAX_LANES:
        return list(range(count))
    if count % chips or MAX_LANES % chips:
        chips = 1
    block = count // chips
    return [k * block + int(i) for k in range(chips)
            for i in sorted(rng.permutation(block)[:MAX_LANES // chips])]


def campaign_numbers(grid, config, mix, outputs, sample, seed_plan,
                     seed: int, control=None, chips: int = 1):
    """Numbers of a campaign cell.  ``sample`` is the sampled job's
    output record, of which the lanes :func:`sample_lanes` draws from
    ``seed`` over ``chips`` are simulated again; ``seed_plan`` is the
    program's BiDOR table (None for XY).  ``control`` (a lower
    precision) adds the numbers of the reference computed in it:
    ``control_argmin_gap`` of its plan and ``control_lanes_differing``
    of its simulation."""
    from .generator import pattern_matrix
    tm = pattern_matrix(grid, mix["pattern"])
    sim = sim_params(config, mix["algo"])
    out = dict(jobs_failed=jobs_failed(outputs),
               lanes_unsound=lanes_unsound(outputs))
    if seed_plan is not None:
        planner = Planner(grid)
        ref = planner.plan(tm)
        out["argmin_gap"] = choice_gap(ref["costs"], seed_plan)
        if control is not None:
            low = planner.plan(tm, dtype=control, device=_chip())
            out["control_argmin_gap"] = choice_gap(ref["costs"],
                                                   low["choice"])
    pts = points(sample["job"])
    keep = [pts[i] for i in sample_lanes(len(pts), chips, seed)]
    ref_stats = rsim.run_campaign_lanes(grid, tm, seed_plan, sim, keep,
                                        config["chunk"])
    got = [sample["results"][pts.index(p)] for p in keep
           if pts.index(p) < len(sample["results"])]
    out["lanes_differing"] = lanes_differing(got, ref_stats)
    if control is not None:
        low = rsim.run_campaign_lanes(grid, tm, seed_plan, sim, keep,
                                      config["chunk"], gen_dtype=control)
        out["control_lanes_differing"] = lanes_differing(low, ref_stats)
    return out


def _chip():
    import jax
    return jax.devices()[0]


def _same(a, b) -> bool:
    if a is None or b is None:
        return a is None and b is None
    return np.array_equal(a, b)


def session_numbers(grid, config, mix, job, out, control=None):
    """Numbers of one sampled control-plane session (``out`` is its
    output record, with the stages the recorder kept).  ``control`` (a
    lower precision) adds ``control_argmin_gap``: the reference planner
    computed in it, on the same inputs, in the program's place."""
    sim = sim_params(config, config["algo"])
    planner, refiner = Planner(grid), Refiner(grid)
    stages = out["stages"]
    seed = [s for s in stages if s["kind"] == "seed"]
    reps = [s for s in stages if s["kind"] == "replan"]
    gap, shed_diff, refine_diff, sched = 0.0, 0, 0, 0
    if len(seed) != 1:
        return dict(schedule_differing=1 + len(reps))
    ref = planner.plan(seed[0]["traffic"])
    gap = choice_gap(ref["costs"], seed[0]["plan"]["choice"])
    prev = ref["w_final"]
    low_gap, low_prev = 0.0, None
    if control is not None:
        low = planner.plan(seed[0]["traffic"], dtype=control, device=_chip())
        low_gap = choice_gap(ref["costs"], low["choice"])
        low_prev = low["w_final"]
    rp = config["replan"]
    replay = rctrl.replay(grid, job["traffic"], sim, rp, config["epoch"],
                          job["events"], points(job),
                          seed[0]["plan"]["choice"],
                          [r["shipped"] for r in reps])
    sched += abs(len(replay["schedule"]) - len(reps))
    sched += replay["extra_shipped"]
    for rec, inp in zip(reps, replay["inputs"]):
        if not (np.array_equal(rec["traffic"], inp["traffic"])
                and np.array_equal(rec["bw"], inp["bw"])):
            sched += 1
        ref = planner.plan(inp["traffic"], bw=inp["bw"],
                           w0=inp["traffic"].sum(1) + prev)
        prev = ref["w_final"]
        if control is not None:
            low = planner.plan(inp["traffic"], bw=inp["bw"],
                               w0=inp["traffic"].sum(1) + low_prev,
                               dtype=control, device=_chip())
            low_prev = low["w_final"]
            low_gap = max(low_gap, choice_gap(ref["costs"], low["choice"],
                                              ref["unroutable"]))
        plan = rec["plan"]
        gap = max(gap, choice_gap(ref["costs"], plan["choice"],
                                  ref["unroutable"]))
        if not _same(plan["unroutable"], ref["unroutable"]):
            a = np.zeros((grid.n, grid.n), bool) if plan["unroutable"] \
                is None else plan["unroutable"]
            b = np.zeros((grid.n, grid.n), bool) if ref["unroutable"] \
                is None else ref["unroutable"]
            shed_diff += int((a != b).sum())
        refined = refiner.refine(inp["traffic"], plan["choice"],
                                 plan["unroutable"], inp["bw"],
                                 rp["greedy_sweeps"])
        refine_diff += int((refined != rec["shipped"]).sum())
    installed = [e for e in replay["schedule"] if not e.get("rejected")]
    prog = out["replans"]
    sched += abs(len(installed) - len(prog))
    for e, p in zip(installed, prog):
        if (e["cycle"], e["trigger"], e["drift_distance"],
                e.get("unroutable_pairs")) != (
                p["cycle"], p["trigger"], p["drift_distance"],
                p["unroutable_pairs"]):
            sched += 1
    numbers = dict(lanes_differing=lanes_differing(out["results"],
                                                   replay["stats"]),
                   argmin_gap=gap, shed_pairs_differing=shed_diff,
                   refine_entries_differing=refine_diff,
                   schedule_differing=sched)
    if control is not None:
        numbers["control_argmin_gap"] = low_gap
    return numbers


def verdict(numbers: dict) -> tuple[bool, dict]:
    """(correct, {name: {value, limit}}) for the numbers a run read."""
    checks = {k: {"value": v, "limit": LIMITS[k]}
              for k, v in numbers.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks
