"""Smoke run of the main path on one TPU: planner, certifier, campaign
service, simulator and control plane, at sizes users of a NoC simulator
call real.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # four chips: lane sharding only

Phases, in one process (a second process could not reach the chip):

1. device check — the first device must be a TPU; there is no CPU
   fallback, so without a chip the script exits non-zero and prints no
   result;
2. goldens — the 4×4 golden campaign (``tests/goldens/regen.py``) on the
   chip.  Its integer flit counts must equal ``campaign_4x4.json``: XY
   points as they are, and BiDOR points once run on the reference choice
   tables (the fp64 plan, computed on the host).  How many entries of
   the chip's own fp32 plan differ from those tables is printed;
3. plans — BiDOR plans of 12×12 and 16×16 meshes (uniform and
   transpose traffic) built on the chip in fp32, against the fp64 plans
   built on the host: the share of choice-table entries that differ is
   printed and must stay under ``PLAN_DIFF_MAX``.  12×12 pads every
   axis of the possibility kernel's blocks, and both span two
   destination blocks;
4. scale — ``run_campaign_service`` with ``resume=False`` under a fresh
   job root: a 32×32 mesh (XY and BiDOR, uniform and transpose, 2 rates
   × 2 seeds) and one XY lane on a 64×64 mesh;
5. control plane — ``run_controlled`` on a 16×16 torus with one link
   failure and one traffic drift under the online policy; at least one
   replan must go through the device planner, the certifier and the hot
   swap.

Every result must conserve flits (``injected == ejected + in_flight``).
With ``--four-chips`` only one campaign runs: 8 lanes through the
shard_map runner over all four chips, and the same lanes pinned to
device 0; the two end states must be bit-identical and the sharded one
spread over every device.

Lines before the last are smoke lines (wall and compile seconds,
simulated lane-cycles per second, dispatch paths), not benchmark
numbers.  The last line is the JSON verdict.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
INT_FIELDS = ("injected", "ejected", "in_flight", "reorder", "meas_cycles")
# fp32 may break a near-tie of route weights the other way than fp64; a
# wrong possibility kernel moves hundreds to tens of thousands of the
# 65,536 entries of a 16×16 table
PLAN_DIFF_MAX = 1e-3


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Network sides and run lengths of the phases."""

    plan_sides: tuple = (12, 16)
    scale_side: int = 32
    scale_rates: tuple = (0.03, 0.06)
    scale_cycles: int = 3000
    big_side: int = 64
    big_cycles: int = 1000
    ctrl_side: int = 16
    ctrl_cycles: int = 3000
    ctrl_epoch: int = 500
    four_side: int = 32
    four_cycles: int = 1000


class _CompileClock:
    """Sums JAX's backend-compile durations while active."""

    def __init__(self):
        self.total = 0.0

    def __call__(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.total += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


def _say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _check_conservation(results, where: str) -> None:
    for r in results:
        _check(r.injected_flits == r.ejected_flits + r.in_flight_flits,
               f"{where}: flits not conserved ({r.injected_flits} injected"
               f" != {r.ejected_flits} ejected + {r.in_flight_flits} in "
               f"flight, rate {r.injection_rate}, seed {r.seed})")
        _check(np.isfinite([r.throughput, r.avg_latency,
                            r.link_load_max]).all(),
               f"{where}: non-finite statistics")


def check_device(platform: str, count: int | None = None):
    """The first device, which must be on ``platform``."""
    devs = jax.devices()
    _check(devs[0].platform == platform,
           f"needs a {platform} device, JAX found {devs[0].platform}")
    _check(count is None or len(devs) == count,
           f"needs {count} {platform} devices, JAX found {len(devs)}")
    return devs[0]


# --------------------------------------------------------------------- #
def phase_goldens() -> None:
    sys.path.insert(0, os.path.join(HERE, "tests", "goldens"))
    import regen
    from repro.core.plan_fast import build_plan_fast, build_plans_batched
    from repro.noc import Algo, run_campaign

    with open(regen.GOLDEN_PATH) as f:
        gold = json.load(f)["points"]
    t0 = time.perf_counter()
    got = regen.compute_goldens()["points"]
    spec = regen.golden_spec()
    items = spec.pattern_items()
    chip = build_plans_batched(spec.topo, [tm for _, tm in items])
    with jax.default_device(jax.devices("cpu")[0]):
        ref = {name: build_plan_fast(spec.topo, tm, precision="fp64",
                                     use_pallas=False).table.choice
               for name, tm in items}
    on_ref = run_campaign(dataclasses.replace(spec, algos=(Algo.BIDOR,)),
                          bidor_tables=ref)
    on_ref = {f"{p.pattern}/{p.algo.name}/r{p.rate}/s{p.seed}": p.result
              for p in on_ref.points}

    def wrong(key, point):
        return [f for f in INT_FIELDS if point[f] != gold[key][f]]

    _check(set(got) == set(gold), "golden point set differs")
    for key in sorted(gold):
        if "/XY/" in key:
            bad = wrong(key, got[key])
            _check(not bad, f"golden {key}: {bad} differ on the chip")
    for (name, _), plan in zip(items, chip):
        diff = int((np.asarray(plan.table.choice) != ref[name]).sum())
        _say("goldens", pattern=name, bidor_table_entries_differing=diff,
             of=ref[name].size)
        for key in sorted(k for k in gold if k.startswith(f"{name}/BIDOR/")):
            r = on_ref[key]
            bad = wrong(key, {"injected": r.injected_flits,
                              "ejected": r.ejected_flits,
                              "in_flight": r.in_flight_flits,
                              "reorder": r.reorder_value,
                              "meas_cycles": r.meas_cycles})
            _check(not bad, f"golden {key} on the reference table: {bad} "
                            f"differ on the chip")
            if diff == 0:
                bad = wrong(key, got[key])
                _check(not bad, f"golden {key}: {bad} differ on the chip "
                                f"with an identical table")
    _say("goldens", points=len(gold), wall_s=time.perf_counter() - t0)


def phase_plans(sizes: Sizes) -> None:
    from repro.core import mesh2d, traffic
    from repro.core.plan_fast import build_plan_fast, build_plans_batched

    for side in sizes.plan_sides:
        topo = mesh2d(side, side)
        items = [(p, traffic.PATTERNS[p](topo))
                 for p in ("uniform", "transpose")]
        chip = build_plans_batched(topo, [tm for _, tm in items])
        with jax.default_device(jax.devices("cpu")[0]):
            ref = [build_plan_fast(topo, tm, precision="fp64",
                                   use_pallas=False).table.choice
                   for _, tm in items]
        for (name, _), plan, want in zip(items, chip, ref):
            diff = int((np.asarray(plan.table.choice) != want).sum())
            _say("plans", nodes=topo.num_nodes, pattern=name,
                 bidor_table_entries_differing=diff, of=want.size)
            _check(diff <= PLAN_DIFF_MAX * want.size,
                   f"plans: {diff} of {want.size} BiDOR table entries of "
                   f"{topo.name} {name} differ from the fp64 plan")


def _service_campaign(spec, label: str) -> None:
    from repro.noc import run_campaign_service
    from repro.noc.sim import build_tables

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root, \
            _CompileClock() as clock:
        t0 = time.perf_counter()
        res, job = run_campaign_service(spec, root=root, resume=False)
        wall = time.perf_counter() - t0
        with open(job.metrics_path) as f:
            events = [json.loads(line) for line in f if line.strip()]
    errors = [e for e in events if e.get("event") == "cell_error"]
    _check(not errors, f"{label}: cells failed: {errors}")
    _check(res is not None, f"{label}: the job did not complete")
    _check(len(res.points) == spec.num_points,
           f"{label}: {len(res.points)} points, expected {spec.num_points}")
    _check_conservation([p.result for p in res.points], label)
    from repro.kernels.simstep import ops as simstep_ops
    _, meta = build_tables(spec.topo, spec.pattern_items()[0][1], None,
                           spec.base.num_vcs)
    path, _, interp = simstep_ops.resolve_path(meta, spec.base)
    _check(not interp, f"{label}: simstep resolved to interpret mode")
    # chunk=0: every cell runs all its cycles, with no early exit
    _say(label, nodes=spec.topo.num_nodes, cells=len(job.cells),
         lanes_per_cell=len(spec.rates) * len(spec.seeds),
         cycles=spec.base.cycles, simstep_path=path, wall_s=wall,
         compile_s=clock.total,
         lane_cycles_per_s=spec.num_points * spec.base.cycles / wall)


def phase_scale(sizes: Sizes) -> None:
    from repro.core import mesh2d
    from repro.noc import Algo, CampaignSpec, SimConfig

    c = sizes.scale_cycles
    _service_campaign(CampaignSpec(
        topo=mesh2d(sizes.scale_side, sizes.scale_side),
        algos=(Algo.XY, Algo.BIDOR), patterns=("uniform", "transpose"),
        rates=sizes.scale_rates, seeds=(0, 1),
        base=SimConfig(cycles=c, warmup=c // 3, drain=c // 10)),
        f"scale{sizes.scale_side}")
    c = sizes.big_cycles
    _service_campaign(CampaignSpec(
        topo=mesh2d(sizes.big_side, sizes.big_side), algos=(Algo.XY,),
        patterns=("uniform",), rates=sizes.scale_rates[:1], seeds=(0,),
        base=SimConfig(cycles=c, warmup=c // 3)),
        f"scale{sizes.big_side}")


def phase_ctrl(sizes: Sizes) -> None:
    from repro.core import torus, traffic
    from repro.noc import (Algo, LinkFail, ReplanConfig, Scenario,
                           SimConfig, TrafficDrift, run_controlled)
    from repro.obs.trace import TraceWriter, read_trace

    k, c = sizes.ctrl_side, sizes.ctrl_cycles
    topo = torus(k, k)
    scen = Scenario(
        "linkfail_drift", policy="online",
        replan=ReplanConfig(epoch=sizes.ctrl_epoch),
        events=(LinkFail(cycle=c // 3, links=((0, 1), (1, 0))),
                TrafficDrift(cycle=2 * c // 3,
                             traffic=traffic.transpose(topo))))
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp, \
            _CompileClock() as clock:
        writer = TraceWriter(os.path.join(tmp, "ctrl_trace.jsonl"))
        t0 = time.perf_counter()
        res = run_controlled(topo, traffic.uniform(topo),
                             SimConfig(algo=Algo.BIDOR, cycles=c,
                                       warmup=c // 6),
                             scen, rates=[0.1], seeds=[0, 1],
                             tracer=writer)
        wall = time.perf_counter() - t0
        writer.close()
        events = read_trace(writer.path)
    _check_conservation(res.results, "ctrl")
    _check(any(r.trigger == "fault" for r in res.replans),
           f"ctrl: no replan after the link failure ({res.replans})")
    # the seed plan, then at least one replan, on the device planner
    builds = sum(e["name"] == "plan_device" for e in events)
    _check(builds >= 2, f"ctrl: {builds} device plan builds")
    _say("ctrl", nodes=topo.num_nodes, lanes=len(res.points), cycles=c,
         replans=",".join(f"{r.trigger}@{r.cycle}" for r in res.replans),
         unroutable_pairs=max(r.unroutable_pairs for r in res.replans),
         wall_s=wall, compile_s=clock.total,
         lane_cycles_per_s=len(res.points) * c / wall)


def phase_four_chips(sizes: Sizes) -> None:
    """Sharded lanes against the same lanes on device 0: bit-identical."""
    from repro.core import mesh2d, traffic
    from repro.noc import Algo, SimConfig
    from repro.noc import sim

    devs = jax.devices()
    topo = mesh2d(sizes.four_side, sizes.four_side)
    cfg = SimConfig(algo=Algo.XY, cycles=sizes.four_cycles,
                    warmup=sizes.four_cycles // 3)
    points = [(r, s) for r in (0.02, 0.04, 0.06, 0.08) for s in (0, 1)]
    tables, meta = sim.build_tables(topo, traffic.uniform(topo), None,
                                    cfg.num_vcs)
    runs = {}
    for label, multi in (("sharded", True), ("device0", False)):
        states = sim.make_states(meta, cfg, points)
        args = (tables, states)
        if not multi:
            args = jax.device_put(args, devs[0])
        runner = sim.get_runner(meta, cfg, cfg.cycles,
                                num_lanes=len(points), multi_device=multi)
        with _CompileClock() as clock:
            t0 = time.perf_counter()
            out = jax.block_until_ready(runner(*args))
            wall = time.perf_counter() - t0
        spread = len(out["fifo_size"].sharding.device_set)
        runs[label] = jax.device_get(out)
        _say("four_chips", run=label, lanes=len(points),
             nodes=topo.num_nodes, cycles=cfg.cycles, devices=spread,
             wall_s=wall, compile_s=clock.total,
             lane_cycles_per_s=len(points) * cfg.cycles / wall)
        _check(spread == (len(devs) if multi else 1),
               f"four_chips: {label} output spans {spread} devices")
    a, b = runs["sharded"], runs["device0"]
    _check(a.keys() == b.keys(), "four_chips: state keys differ")
    bad = sorted(k for k in a if not np.array_equal(a[k], b[k]))
    _check(not bad, f"four_chips: sharded != device 0 in {bad}")
    _check_conservation(
        [sim.postprocess(jax.tree.map(lambda x: x[i], a), cfg, topo,
                         rate=r, seed=s)
         for i, (r, s) in enumerate(points)], "four_chips")
    _say("four_chips", bit_identical=True, state_arrays=len(a))


# --------------------------------------------------------------------- #
def main(argv=None, *, sizes: Sizes = Sizes(), platform: str = "tpu"):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the lane-sharded campaign on 4 chips")
    args = ap.parse_args(argv)
    dev = check_device(platform, 4 if args.four_chips else None)
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.compile_cache import use_checkout_cache

    _say("device", platform=dev.platform, kind=dev.device_kind,
         count=len(jax.devices()),
         compile_cache=use_checkout_cache(HERE))
    phases = ([("four_chips", lambda: phase_four_chips(sizes))]
              if args.four_chips else
              [("goldens", phase_goldens),
               ("plans", lambda: phase_plans(sizes)),
               ("scale", lambda: phase_scale(sizes)),
               ("ctrl", lambda: phase_ctrl(sizes))])
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        _say(name, phase_wall_s=time.perf_counter() - t0)
    verdict = {"ok": True, "device": {"platform": dev.platform,
                                      "kind": dev.device_kind,
                                      "count": len(jax.devices())}}
    print(json.dumps(verdict), flush=True)
    return verdict


if __name__ == "__main__":
    try:
        main()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
