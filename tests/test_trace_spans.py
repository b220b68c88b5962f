"""Spans inside the control plane: every replan decomposes into the
planner, BiDOR-G, the certifier and the hot swap, each child inside its
parent and tagged with the replan's ordinal; the spans reach the
profiler's host plane; tracing changes no result."""

import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.core import torus, traffic
from repro.core.routes import dimension_orders, walk_routes
from repro.noc import (Algo, LinkFail, LinkRecover, ReplanConfig, Scenario,
                       SimConfig, ctrl, run_controlled)
from repro.obs.trace import (NULL_TRACER, TraceWriter, read_trace, span,
                             tagged, validate_events)

TOPO = torus(4, 4)
LINK = ((0, 1), (1, 0))
TOL_US = 1.0     # float µs timestamps: a sum may round by a fraction of one


def _session(tracer=None):
    cfg = SimConfig(algo=Algo.BIDOR, cycles=1000, warmup=200, drain=0,
                    injection_rate=0.2)
    scen = Scenario("flap", events=(LinkFail(300, LINK),
                                    LinkRecover(600, LINK)),
                    policy="online", replan=ReplanConfig(epoch=200))
    return run_controlled(TOPO, traffic.uniform(TOPO), cfg, scen,
                          rates=[0.2], seeds=[0], tracer=tracer)


def _inside(child, parent):
    return (parent["ts"] - TOL_US <= child["ts"]
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + TOL_US)


def _kids(events, parent, name, **args):
    return [e for e in events if e["name"] == name and e is not parent
            and _inside(e, parent)
            and all(e["args"].get(k) == v for k, v in args.items())]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced session, and the inputs BiDOR-G saw at each replan."""
    seen = []
    orig = ctrl.greedy_refine

    def greedy_refine(topo, t, table, **kw):
        seen.append((np.array(t), None if table.unroutable is None
                     else np.array(table.unroutable)))
        return orig(topo, t, table, **kw)

    path = str(tmp_path_factory.mktemp("spans") / "trace.jsonl")
    ctrl.greedy_refine = greedy_refine
    try:
        writer = TraceWriter(path)
        res = _session(writer)
        writer.close()
    finally:
        ctrl.greedy_refine = orig
    return res, read_trace(path), seen


def test_each_replan_decomposes_into_its_stages(traced):
    res, events, _ = traced
    assert validate_events(events) == []
    replans = [e for e in events if e["name"] == "replan"]
    assert len(replans) == len(res.replans) >= 2
    assert {r["args"]["trigger"] for r in replans} == {"fault"}
    for r in replans:
        k = r["args"]["replan"]
        (bpf,) = _kids(events, r, "build_plan_fast")
        assert bpf["args"]["warm"] is True
        for stage in ("plan_statics", "plan_device", "plan_assemble"):
            (s,) = _kids(events, bpf, stage)
            assert s["args"]["replan"] == k
        (gate,) = _kids(events, bpf, "certify", label="build_plan_fast")
        (ref,) = _kids(events, r, "greedy_refine")
        (cert,) = _kids(events, r, "certify", label="replan")
        (swap,) = _kids(events, r, "hot_swap")
        assert swap["args"]["rejected"] is False
        for child in (bpf, gate, ref, cert, swap):
            assert child["args"]["replan"] == k
        # the stages follow one another in the replan's order
        assert bpf["ts"] + bpf["dur"] <= ref["ts"] + TOL_US
        assert ref["ts"] + ref["dur"] <= cert["ts"] + TOL_US
        assert cert["ts"] + cert["dur"] <= swap["ts"] + TOL_US
    assert len({r["args"]["replan"] for r in replans}) == len(replans)


def test_greedy_refine_counts_the_pairs_it_sweeps(traced):
    _, events, seen = traced
    refines = [e for e in events if e["name"] == "greedy_refine"]
    assert len(refines) == len(seen)
    eye = np.eye(TOPO.num_nodes, dtype=bool)
    for ev, (t, unroutable) in zip(refines, seen):
        want = (t > 0) & ~eye
        if unroutable is not None:
            want &= ~unroutable
        assert ev["args"]["pairs"] == int(want.sum()) > 0
        assert 1 <= ev["args"]["sweeps_run"] <= 2
        assert 0 <= ev["args"]["changed"] <= ev["args"]["pairs"]


def test_greedy_refine_visits_only_the_pairs_that_could_flip(traced):
    _, events, seen = traced
    refines = [e for e in events if e["name"] == "greedy_refine"]
    xy, yx = (walk_routes(TOPO, o)
              for o in dimension_orders(2, binary_only=True))
    same_route = (xy == yx).all(-1)            # dx == 0 or dy == 0
    eye = np.eye(TOPO.num_nodes, dtype=bool)
    for ev, (t, unroutable) in zip(refines, seen):
        want = (t > 0) & ~eye & ~same_route
        if unroutable is not None:
            want &= ~unroutable
        assert ev["args"]["visited"] == int(want.sum())
        assert 0 < ev["args"]["visited"] <= ev["args"]["pairs"]
    # one fabric throughout: every replan after the first finds its
    # routes cached
    assert all(e["args"]["route_cache_hit"] is True for e in refines[1:])


def test_boundaries_and_epochs_carry_their_counts(traced):
    _, events, _ = traced
    epochs = [e for e in events if e["name"] == "epoch"]
    bounds = [e for e in events if e["name"] == "boundary"]
    # epochs of 200 cycles, split at the events (cycles 300 and 600)
    lengths = [e["args"]["cycles"] for e in epochs]
    assert lengths == [200, 100, 100, 200, 200, 200]
    assert len(bounds) == len(epochs)
    # a runner is built at most once per length (the first epoch of a
    # length may find it built by an earlier test in the process)
    assert not any(e["args"]["compiled"] for i, e in enumerate(epochs)
                   if lengths[i] in lengths[:i])
    n = TOPO.num_nodes
    for b in bounds:
        assert b["args"]["host_bytes"] >= 4 * n * n   # int32 pair counters
        assert 0 < b["args"]["nonzero_pairs"] <= n * (n - 1)
    for b, e in zip(bounds, epochs):
        assert e["ts"] + e["dur"] <= b["ts"] + TOL_US


def test_tracing_changes_no_result(traced):
    res, _, _ = traced
    plain = _session(NULL_TRACER)
    assert [dataclasses.astuple(r) for r in res.replans] == \
        [dataclasses.astuple(r) for r in plain.replans]
    for a, b in zip(res.results, plain.results):
        da, db = dataclasses.asdict(a), dataclasses.asdict(b)
        assert np.array_equal(da.pop("node_load"), db.pop("node_load"))
        assert da == db


def test_spans_reach_the_profilers_host_plane(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        _session()        # no tracer: the annotations stand alone
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for p in data.planes if p.name.startswith("/host:")
             for ln in p.lines for e in ln.events}
    assert {"replan", "greedy_refine", "plan_device", "hot_swap",
            "epoch", "boundary"} <= names


class _Collect:
    enabled = True

    def __init__(self):
        self.events = []

    def now_us(self):
        return 0.0

    def complete(self, name, ts_us, dur_us, **kw):
        self.events.append((name, kw.get("args")))

    def instant(self, name, **kw):
        self.events.append((name, kw.get("args")))

    def counter(self, name, values, **kw):
        pass


def test_span_helper_carries_found_args_tags_and_drops():
    col = _Collect()
    t = tagged(col, replan=3)
    with span(t, "outer", cycle=7) as a:
        a["pairs"] = 5
        with span(t, "inner") as b:
            b.drop()
    with pytest.raises(ValueError):
        with span(t, "failing"):
            raise ValueError("boom")
    t.instant("mark", args={"x": 1})
    assert col.events == [("outer", {"replan": 3, "cycle": 7, "pairs": 5}),
                          ("failing", {"replan": 3, "error": True}),
                          ("mark", {"replan": 3, "x": 1})]
    assert tagged(NULL_TRACER, replan=1) is NULL_TRACER


def test_report_renders_the_hot_swap_span(tmp_path):
    import csv

    from repro.core import mesh2d
    from repro.noc import CampaignSpec, run_campaign_service
    from repro.obs.report import render_job

    topo = mesh2d(3, 3)
    spec = CampaignSpec(
        topo=topo, algos=(Algo.BIDOR,), patterns=("uniform",),
        rates=(0.2,), seeds=(0,),
        base=SimConfig(cycles=800, warmup=200, drain=0),
        scenarios=(Scenario("fail", events=(LinkFail(400, LINK),),
                            policy="online",
                            replan=ReplanConfig(epoch=200)),))
    res, job = run_campaign_service(spec, root=str(tmp_path / "jobs"),
                                    trace=True)
    assert res is not None
    summary = render_job(job.dir, str(tmp_path / "obs"))
    assert summary["replans"] >= 1
    with open(tmp_path / "obs" / "replan_timeline.csv") as f:
        rows = list(csv.DictReader(f))
    swaps = [r for r in rows if r["name"] == "hot_swap"]
    assert len(swaps) == summary["replans"]
    assert all(r["ph"] == "X" and float(r["dur_us"]) > 0 for r in swaps)
    assert all('"rejected": false' in r["args"] for r in swaps)


def test_a_rejected_replan_leaves_no_replan_span():
    from repro.noc.chaos import region_links

    dark = (LinkFail(cycle=400, links=region_links(TOPO, 5, 1),
                     bw_scale=0.0),)
    col = _Collect()
    res = run_controlled(
        TOPO, traffic.uniform(TOPO),
        SimConfig(algo=Algo.BIDOR, cycles=800, warmup=200, drain=0,
                  injection_rate=0.2),
        Scenario("dark", events=dark, policy="online",
                 replan=ReplanConfig(epoch=200, max_shed=0.05)),
        tracer=col)
    assert res.replans == []
    names = [n for n, _ in col.events]
    assert "replan" not in names and "hot_swap_rejected" in names
    (swap,) = [a for n, a in col.events if n == "hot_swap"]
    assert swap["rejected"] is True and swap["shed_pairs"] > 0
    assert swap["replan"] == 0


@pytest.mark.parametrize("dims", [(4, 4, 4), (6, 6)])
def test_counters_of_the_fabric_dimensions(dims):
    """``plan_statics`` counts the fabric's dimensions and BiDOR's
    orders, ``greedy_refine`` its orders and the route hops it weighs,
    ``certify`` the dimensions with a dateline layer; on a 3-D and a
    2-D torus."""
    from repro.core.certify import certify_table
    from repro.core.plan_fast import build_plan_fast

    topo = torus(*dims)
    t = traffic.uniform(topo)
    col = _Collect()
    plan = build_plan_fast(topo, t, tracer=col)
    with span(col, "greedy_refine") as a:
        ctrl.greedy_refine(topo, t, plan.table, sweeps=2, stats=a)
    certify_table(topo, plan.table, tracer=col, label="check")
    args = {}
    for name, arg in col.events:
        args.setdefault(name, []).append(arg)
    (statics,) = args["plan_statics"]
    assert (statics["ndim"], statics["orders"]) == (len(dims), 2)
    assert [c["wrap_dims"] for c in args["certify"]] == [len(dims)] * 2
    # BiDOR-G visits the pairs that differ in two dimensions or more,
    # and weighs both orders' routes, each as long as the pair's
    # distance round the rings
    (refine,) = args["greedy_refine"]
    delta = np.abs(topo.coords[:, None] - topo.coords[None])
    ring = np.minimum(delta, np.asarray(dims) - delta).sum(-1)
    visited = (t > 0) & ((delta != 0).sum(-1) >= 2)
    assert refine["orders"] == 2
    assert refine["visited"] == int(visited.sum()) > 0
    assert refine["hops"] == 2 * int(ring[visited].sum())
