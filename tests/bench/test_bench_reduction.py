"""The benchmark's yardsticks: the trace reduction, the possibility
pass's operation and byte counts, and the reference planner and
simulator against the program at small sizes."""

from __future__ import annotations

import dataclasses
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench_tiny import REPO

RECORDED = os.path.join(REPO, "tests", "bench", "data")


def _recorded_planes():
    """The planes of the trace recorded on the chip, read as the
    harness reads its own: from the serialized bytes."""
    import glob

    from qsbench import xtrace
    path, = glob.glob(os.path.join(RECORDED, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    with open(path, "rb") as f:
        return xtrace.from_bytes(f.read())


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench_window", 1000, 10000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_run(1)", 1000, 4000),
                                       _ev("jit_core(2)", 8000, 1000)]),
        NS(name="XLA Ops", events=[
            _ev("%fusion.1 = s32[2,256]{1,0} fusion(%p.1), kind=kLoop",
                1000, 2000),
            _ev("%fusion.2 = f32[4]{0} fusion(%p.2)", 3000, 2000),
            _ev("%custom-call.3 = f32[256,256]{1,0} custom-call(%a, %b), "
                'custom_call_target="tpu_custom_call", '
                'backend_config="{}"', 8000, 1000),
            _ev("%fusion.1 = s32[2,256]{1,0} fusion(%p.1), kind=kLoop",
                500, 200)])])
    other = NS(name="/device:CUSTOM:Megascale Trace", lines=[])
    return [host, dev, other]


def test_reduce_busy_idle_and_gaps():
    from qsbench import xtrace
    # host clock: the window opened at 50 µs on the host (50_000 ns)
    spans = [dict(name="job", ts=50.0, dur=10.0),
             dict(name="replan", ts=55.5, dur=2.0)]
    r = xtrace.reduce(_planes(), window_unix_ns=50_000, host_spans=spans)
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx(5e-6)
    assert r["modules"]["jit_run(1)"] == pytest.approx(4e-6)
    assert r["op_counts"] == {"%fusion.1": 1, "%fusion.2": 1,
                              "%custom-call.3[tpu_custom_call]": 1}
    # gaps: [5000, 8000) inside the replan span, [9000, 11000) in the job
    assert r["idle_gaps"][0] == ["replan", pytest.approx(3e-6)]
    assert r["idle_gaps"][1] == ["job", pytest.approx(2e-6)]
    assert r["device_ops"][0] == ["%fusion.1", pytest.approx(2e-6)]
    # where the longest gaps start, and the last op's end, from the window
    assert r["gaps_at"][0] == ["replan", pytest.approx(3e-6),
                               pytest.approx(4e-6)]
    assert r["last_op_s"] == pytest.approx(8e-6)


def test_reduce_four_chips_sums_runner_time_and_averages_idle():
    """Four device planes, each running its shard of the lanes for a
    different time: busy time and the idle share are averaged over the
    chips, the runner's device time per lane-cycle is summed over them,
    and each chip is listed with its busy time and its runner module."""
    from qsbench import harness, xtrace
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench_window", 0, 10000)])])
    devs = []
    for i, dur in enumerate((8000, 6000, 4000, 2000)):
        devs.append(NS(name=f"/device:TPU:{i}", lines=[
            NS(name="XLA Modules", events=[_ev("jit_run(7)", 1000, dur)]),
            NS(name="XLA Ops", events=[
                _ev("%while.1 = (s32[4]) while(%t)", 1000, dur)])]))
    r = xtrace.reduce([host] + devs)
    assert r["devices"] == 4
    assert r["busy_s"] == pytest.approx(5e-6)
    assert r["modules"]["jit_run(7)"] == pytest.approx(5e-6)
    assert r["op_counts"] == {"%while.1": 4}
    assert [d[0] for d in r["by_device"]] == [p.name for p in devs]
    assert [d[1] for d in r["by_device"]] == pytest.approx(
        [8e-6, 6e-6, 4e-6, 2e-6])
    assert [d[2] for d in r["by_device"]] == [
        {"jit_run(7)": pytest.approx(v)} for v in (8e-6, 6e-6, 4e-6, 2e-6)]
    run = harness.Run(workload="w", config={}, mix={}, setup_s=0.0,
                      window_s=r["window_s"],
                      jobs=[dict(lanes=16, cycles=1000, cells_wall_s=[])],
                      spans=[], trace=r, peaks={})
    # 20 us of runner time on all chips over 16,000 lane-cycles
    assert harness.load_metric("step_device_us_per_lane_cycle").read(
        run) == pytest.approx(1e6 * 20e-6 / 16000)
    assert harness.load_metric("device_idle_share.campaign").read(
        run) == pytest.approx(50.0)


def test_reduce_refuses_a_trace_without_window_or_device():
    from qsbench import xtrace
    planes = _planes()
    with pytest.raises(ValueError):
        xtrace.reduce(planes[1:])
    with pytest.raises(ValueError):
        xtrace.reduce([planes[0], planes[2]])


def test_in_memory_trace_holds_the_window():
    """The traced run keeps its profile in memory: the session's bytes
    parse to planes that hold the window annotation."""
    import jax
    import jax.numpy as jnp
    from jax._src.lib import _profiler

    from qsbench import xtrace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    session = _profiler.ProfilerSession(opts)
    with jax.profiler.TraceAnnotation(xtrace.WINDOW):
        jnp.arange(8).sum().block_until_ready()
    names = {e.name for p in xtrace.from_bytes(session.stop())
             if p.name.startswith("/host:") for ln in p.lines
             for e in ln.events}
    assert xtrace.WINDOW in names


def test_reduce_recorded_chip_trace():
    """A trace recorded on one TPU v5e chip: one plan of the 16×16 torus
    (the planner's program and its Pallas possibility kernel) and one
    20-cycle runner call, inside the window annotation."""
    import json

    from qsbench import harness, xtrace
    r = xtrace.reduce(_recorded_planes())
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert any(k.startswith("jit_run") for k in r["modules"])
    assert any(k.startswith("jit_core") for k in r["modules"])
    kernel = [k for k in r["ops"] if "possibility_v_pallas" in k]
    assert kernel and all(k.endswith("[tpu_custom_call]") for k in kernel)
    assert r["device_ops"] and r["idle_gaps"]
    with open(os.path.join(REPO, "bench", "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    run = harness.Run(workload="w", config={"dims": [16, 16]}, mix={},
                      setup_s=0.0, window_s=r["window_s"], jobs=[],
                      spans=[], trace=r, peaks=peaks)
    share = harness.load_metric("possibility_roofline").read(run)
    assert 0 < share <= 100


@pytest.mark.parametrize("n,c", [(16, 16), (16, 48), (144, 528),
                                 (256, 1024)])
def test_possibility_work_hand_count(n, c):
    from qsbench.roofline import possibility_work
    ops, nbytes = possibility_work(n, c)
    assert ops == 2 * c * n * n
    # du (n, c) + dn (c, n) + traffic (n, n) + dist (n, n) + V (c, n)
    assert nbytes == 4 * (n * c + c * n + n * n + n * n + c * n)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_possibility_pass_charged_from_its_operands(monkeypatch,
                                                    use_pallas):
    """The jnp path and the Pallas kernel receive the same (N, C) in a
    plan — the nodes, C = N — so both are charged the same work."""
    import repro.core.plan_fast as pf
    from repro.core import mesh2d, traffic
    from qsbench.roofline import possibility_work

    seen = []
    orig = pf._possibility_v

    def spy(dist, t, us, ns, offset, block, up):
        seen.append((int(dist.shape[0]), int(us.shape[0]), bool(up)))
        return orig(dist, t, us, ns, offset, block, up)
    monkeypatch.setattr(pf, "_possibility_v", spy)
    topo = mesh2d(4, 5)
    pf._STATICS_CACHE.clear()
    pf.build_plan_fast(topo, traffic.uniform(topo), use_pallas=use_pallas,
                       precision="fp32")
    pf._STATICS_CACHE.clear()
    assert seen == [(20, 20, use_pallas)]
    assert possibility_work(*seen[0][:2]) == possibility_work(20, 20)


@pytest.mark.parametrize("kind,side,pattern", [
    ("mesh", 6, "transpose"), ("torus", 6, "uniform"),
    pytest.param("torus", (4, 4, 4), "uniform", id="torus-4x4x4-uniform"),
    pytest.param("torus", (4, 4, 4), "transpose",
                 id="torus-4x4x4-transpose")])
def test_reference_planner_matches_program_fp64(kind, side, pattern):
    from qsbench.generator import pattern_matrix
    from qsbench.ref.grid import make_grid
    from qsbench.ref.planner import TIE_TOL, Planner, Refiner, choice_gap
    from repro.core import mesh2d, torus
    from repro.core.bidor import greedy_refine
    from repro.core.plan_fast import build_plan_fast

    dims = (side, side) if isinstance(side, int) else side
    topo = (mesh2d if kind == "mesh" else torus)(*dims)
    grid = make_grid(kind, dims)
    assert np.array_equal(grid.channels(), topo.channels)
    tm = pattern_matrix(grid, pattern)
    bw = np.ones(topo.num_channels)
    bw[[2, 3, 9]] = 0.0
    ptopo = dataclasses.replace(topo, channel_bw=bw)
    plan = build_plan_fast(ptopo, tm, down_channels=np.flatnonzero(bw == 0),
                           precision="fp64", use_pallas=False)
    ref = Planner(grid).plan(tm, bw=bw)
    assert np.array_equal(ref["choice"], plan.table.choice)
    assert np.array_equal(ref["unroutable"], plan.table.unroutable)
    assert ref["iterations"] == plan.nrank.iterations
    # eq. 10 takes the first order within TIE_TOL of the cheapest: the
    # 4x4x4 torus under these faults has such near-ties (about 6e-6),
    # the 6x6 fabrics only exact ones
    limit = 1e-12 if grid.ndim == 2 else TIE_TOL
    assert choice_gap(ref["costs"], plan.table.choice,
                      ref["unroutable"]) <= limit
    refined = Refiner(grid).refine(tm, plan.table.choice,
                                   plan.table.unroutable, bw, 2)
    assert np.array_equal(refined, greedy_refine(ptopo, tm, plan.table,
                                                 sweeps=2).choice)


def test_choice_gap_prices_ties_and_errors():
    from qsbench.ref.planner import choice_gap
    costs = np.array([[[0, 1.0], [1.0, 0]], [[0, 1.0 + 1e-6], [2.0, 0]]])
    assert choice_gap(costs, np.array([[0, 1], [0, 0]])) == pytest.approx(
        1e-6 / 2)
    assert choice_gap(costs, np.array([[0, 0], [1, 0]])) == pytest.approx(
        1.0 / 2)
    costs[1, 1, 0] = np.inf
    assert choice_gap(costs, np.array([[0, 0], [1, 0]])) == np.inf
