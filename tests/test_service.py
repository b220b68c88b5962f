"""Campaign service: kill-and-resume bit-identity, persistent plan
caching, mid-cell control-loop checkpointing, and the multi-axis result
accessors the service streams into."""

import copy
import json
import os

import numpy as np
import pytest

from repro.core import build_plan, mesh2d, torus, traffic
from repro.noc import (Algo, CampaignSpec, LinkFail, ReplanConfig,
                       Scenario, SimConfig, TrafficDrift, run_campaign,
                       run_campaign_service, run_controlled)
from repro.noc.service import CampaignJob, CellCheckpoint, spec_fingerprint

TOPO = mesh2d(3, 3)
UNI = traffic.uniform(TOPO)
BASE = SimConfig(cycles=1200, warmup=300, drain=100)

# full bidirectional link between nodes 0 and 1
LINK01 = ((0, 1), (1, 0))

SCALAR_FIELDS = ("injected_flits", "ejected_flits", "in_flight_flits",
                 "reorder_value", "meas_cycles", "saturated",
                 "avg_latency", "max_latency", "throughput", "offered",
                 "lcv", "p50_latency", "p90_latency", "p99_latency",
                 "link_load_max")


def _spec(**kw):
    d = dict(
        topo=TOPO, algos=(Algo.XY, Algo.BIDOR),
        patterns=(("uni", UNI),), rates=(0.1, 0.3), seeds=(0,),
        base=BASE,
        scenarios=(Scenario("calm"),
                   Scenario("fail", events=(LinkFail(600, LINK01),),
                            policy="oracle",
                            replan=ReplanConfig(epoch=400))))
    d.update(kw)
    return CampaignSpec(**d)


def _assert_points_identical(pts_a, pts_b):
    assert len(pts_a) == len(pts_b)
    for p, q in zip(pts_a, pts_b):
        assert (p.algo, p.pattern, p.rate, p.seed, p.scenario, p.topo) \
            == (q.algo, q.pattern, q.rate, q.seed, q.scenario, q.topo)
        for f in SCALAR_FIELDS:
            assert getattr(p.result, f) == getattr(q.result, f), f
        assert np.array_equal(p.result.node_load, q.result.node_load)


def test_kill_and_resume_is_bit_identical(tmp_path):
    """A job interrupted after every single cell and resumed to the end
    must produce the same CSV byte-for-byte, and the same result
    bit-for-bit, as an uninterrupted job and as plain run_campaign."""
    spec = _spec()
    root = str(tmp_path)
    runs = 0
    while True:
        res, job = run_campaign_service(spec, root=root, job_id="itr",
                                        max_cells=1)
        runs += 1
        assert runs <= 16, "job failed to converge"
        if res is not None:
            break
    # exactly one executed cell per invocation
    assert runs == len(job.cells)

    fres, fjob = run_campaign_service(spec, root=root, job_id="fresh")
    with open(job.csv_path, "rb") as a, open(fjob.csv_path, "rb") as b:
        assert a.read() == b.read()
    _assert_points_identical(res.points, fres.points)
    # the job directory alone reconstructs the result
    _assert_points_identical(res.points, job.result().points)
    # and the service is transparent w.r.t. the blocking engine
    ref = run_campaign(spec)
    _assert_points_identical(res.points, ref.points)


def test_job_refuses_foreign_spec_and_fingerprint_is_content_keyed(
        tmp_path):
    spec = _spec()
    root = str(tmp_path)
    CampaignJob(spec, root=root, job_id="j")
    # same content -> same fingerprint, even through a copy
    assert spec_fingerprint(copy.deepcopy(spec)) == spec_fingerprint(spec)
    # different content (one extra rate) -> refused in the same dir
    other = _spec(rates=(0.1, 0.3, 0.5))
    assert spec_fingerprint(other) != spec_fingerprint(spec)
    with pytest.raises(ValueError, match="different campaign"):
        CampaignJob(other, root=root, job_id="j")


def test_warm_plan_cache_skips_all_plan_builds(tmp_path, monkeypatch):
    """Re-running a spec against a warm shared plan cache must make ZERO
    build_plans_batched calls — the campaign pre-screens every needed
    plan against the cache before batching the misses."""
    import repro.noc.campaign as campaign_mod

    calls = []
    real = campaign_mod.build_plans_batched

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(campaign_mod, "build_plans_batched", counting)
    spec = CampaignSpec(topo=TOPO, algos=(Algo.BIDOR,),
                        patterns=(("uni", UNI),), rates=(0.1,),
                        seeds=(0,), base=BASE)
    root = str(tmp_path)
    res_cold, job_cold = run_campaign_service(spec, root=root,
                                              job_id="cold")
    assert calls, "cold run must build plans on device"
    assert job_cold.plan_cache.stats.stores > 0

    calls.clear()
    res_warm, job_warm = run_campaign_service(spec, root=root,
                                              job_id="warm")
    assert calls == [], "warm run re-built plans despite the cache"
    st = job_warm.plan_cache.stats.as_dict()
    assert st["device_builds"] == 0
    assert st["misses"] == 0
    assert st["hits"] > 0
    # cached plans route identically to freshly built ones
    _assert_points_identical(res_cold.points, res_warm.points)


def test_midcell_checkpoint_resumes_bit_identically(tmp_path):
    """Interrupting a controlled run at an epoch boundary and resuming
    from the snapshot must reproduce the uninterrupted run exactly —
    every lane statistic, the link peaks, and the replan log."""
    topo = mesh2d(3, 3)
    tm = traffic.uniform(topo)
    drift = traffic.tornado(topo)
    cfg = SimConfig(algo=Algo.BIDOR, cycles=2000, warmup=400, drain=200)
    scen = Scenario("dyn",
                    events=(LinkFail(700, LINK01),
                            TrafficDrift(1200, drift)),
                    policy="oracle", replan=ReplanConfig(epoch=400))
    plan = build_plan(topo, tm)
    kw = dict(rates=[0.1, 0.3], seeds=[0], bidor_table=plan.table)

    class Rec:
        """In-memory checkpointer: records every snapshot, optionally
        preloaded with one to resume from."""

        def __init__(self, preload=None):
            self.snaps = []
            self.preload = preload

        def save(self, arrays, meta):
            self.snaps.append(
                ({k: np.array(v) for k, v in arrays.items()},
                 json.loads(json.dumps(meta))))

        def load(self):
            return self.preload

    rec = Rec()
    base = run_controlled(topo, tm, cfg, scen, checkpoint=rec, **kw)
    assert len(rec.snaps) >= 3
    assert base.replans, "oracle policy must have replanned"

    plain = run_controlled(topo, tm, cfg, scen, **kw)

    def check(r):
        assert r.epoch_bounds == base.epoch_bounds
        assert [dataclasses_tuple(x) for x in r.replans] \
            == [dataclasses_tuple(x) for x in base.replans]
        assert np.array_equal(r.link_peak, base.link_peak)
        for a, b in zip(r.results, base.results):
            for f in SCALAR_FIELDS:
                assert getattr(a, f) == getattr(b, f), f
            assert np.array_equal(a.node_load, b.node_load)

    import dataclasses as _dc

    def dataclasses_tuple(x):
        return _dc.astuple(x)

    check(plain)  # recording a snapshot must not perturb the run
    # resume from a mid-run snapshot (after the fault replan) and from
    # the last one — both land on the identical final state
    for snap in (rec.snaps[1], rec.snaps[-1]):
        r = run_controlled(topo, tm, cfg, scen, checkpoint=Rec(snap),
                           **kw)
        check(r)
    # and through the on-disk npz round-trip the service actually uses
    ck = CellCheckpoint(str(tmp_path / "snap.npz"))
    ck.save(*rec.snaps[1])
    r = run_controlled(topo, tm, cfg, scen, checkpoint=ck, **kw)
    check(r)
    ck.clear()
    assert ck.load() is None


def test_multi_axis_grid_matches_per_axis_recomputation():
    """grid()/mean_over_seeds()/saturation_throughput() on a 2-topo ×
    2-scenario campaign agree with manual recomputation from select()
    on every (scenario, topo) pair."""
    spec = CampaignSpec(
        topo=None, topos=(TOPO, torus(3, 3)), algos=(Algo.XY,),
        patterns=("uniform",), rates=(0.1, 0.3), seeds=(0, 1),
        base=BASE,
        scenarios=(Scenario("calm"),
                   Scenario("fail", events=(LinkFail(600, LINK01),))))
    res = run_campaign(spec)
    assert len(res.points) == 2 * 2 * 2 * 2  # topo x scen x rate x seed
    for tname in res.topo_names:
        for sname in res.scenario_names:
            g = res.grid("throughput", Algo.XY, "uniform",
                         scenario=sname, topo=tname)
            assert g.shape == (2, 2)
            for i, rate in enumerate(spec.rates):
                for j, seed in enumerate(spec.seeds):
                    (p,) = res.select(algo=Algo.XY, pattern="uniform",
                                      rate=rate, seed=seed,
                                      scenario=sname, topo=tname)
                    assert g[i, j] == p.result.throughput
            m = res.mean_over_seeds("throughput", Algo.XY, "uniform",
                                    scenario=sname, topo=tname)
            assert np.array_equal(m, g.mean(axis=1))
            sat = res.saturation_throughput(Algo.XY, "uniform",
                                            scenario=sname, topo=tname)
            assert sat == g.mean(axis=1).max()
    # the two topologies genuinely differ (guards against the pooled
    # last-write-wins bug resurfacing as identical grids)
    g_mesh = res.grid("avg_latency", Algo.XY, "uniform",
                      scenario="calm", topo=TOPO.name)
    g_torus = res.grid("avg_latency", Algo.XY, "uniform",
                       scenario="calm", topo=torus(3, 3).name)
    assert not np.array_equal(g_mesh, g_torus)


# ------------------------------------------------------------------ #
# flight recorder: metrics stream, live status, telemetry persistence
# ------------------------------------------------------------------ #
def _metrics(job):
    from repro.obs.report import load_metrics
    return load_metrics(job.metrics_path)


def test_metrics_stream_survives_kill_and_resume(tmp_path):
    """metrics.jsonl is a truthful progress stream: a budget-paused job
    records job_pause; the resume rewrites the stream with the completed
    cells marked cached and ends in job_done with done == total."""
    spec = _spec(base=BASE.replace(telemetry=True, tel_slots=6))
    root = str(tmp_path)
    res, job = run_campaign_service(spec, root=root, job_id="m",
                                    max_cells=2)
    assert res is None
    m = _metrics(job)
    assert m[0]["event"] == "job_start"
    assert m[-1]["event"] == "job_pause" and m[-1]["executed"] == 2
    cells = [r for r in m if r["event"] == "cell"]
    assert len(cells) == 2 and not any(r["cached"] for r in cells)
    assert [r["done"] for r in cells] == [1, 2]
    assert all(r["wall_s"] > 0 and "lanes_per_s" in r for r in cells)

    res, job = run_campaign_service(spec, root=root, job_id="m")
    assert res is not None
    m = _metrics(job)
    assert m[-1]["event"] == "job_done"
    cells = [r for r in m if r["event"] == "cell"]
    assert len(cells) == len(job.cells)
    assert [r["cached"] for r in cells[:2]] == [True, True]
    assert cells[-1]["done"] == len(job.cells)
    # plan-cache stats ride each record
    assert all("plan_cache" in r for r in cells)
    # ETA appears once a wall sample exists and cells remain
    fresh = [r for r in cells if not r["cached"]]
    assert all("eta_s" in r for r in fresh[1:-1])


def test_telemetry_persisted_per_cell_and_fingerprint_excludes_obs(
        tmp_path):
    """Telemetry rides the job as per-cell npz artifacts, and toggling
    it must NOT change the spec fingerprint — probe collection is
    bit-identity-neutral, so the same job resumes either way."""
    import os
    base_on = BASE.replace(telemetry=True, tel_slots=6)
    spec_on = _spec(base=base_on)
    assert spec_fingerprint(spec_on) == spec_fingerprint(_spec())
    assert spec_fingerprint(_spec(base=BASE.replace(tel_slots=99))) \
        == spec_fingerprint(_spec())
    # telemetry-off cells completed earlier must satisfy a telemetry-on
    # resume without re-running: results are the bit-identical truth
    root = str(tmp_path)
    res_off, job_off = run_campaign_service(_spec(), root=root,
                                            job_id="t", max_cells=2)
    res_on, job_on = run_campaign_service(spec_on, root=root, job_id="t")
    assert res_on is not None
    done = {k.slug for k in job_on.completed_cells()}
    assert len(done) == len(job_on.cells)
    for i, key in enumerate(job_on.cells):
        tel = job_on.cell_telemetry(key)
        if i < 2:       # ran with telemetry off: no probe artifact
            assert tel is None
        else:
            assert tel is not None
            assert tel.num_lanes == len(job_on.executor.points)
            assert tel.cycles.sum(axis=1).tolist() \
                == [BASE.cycles] * tel.num_lanes
            assert tel.bw is not None
    # telemetry-on results equal the telemetry-off reference
    ref = run_campaign(_spec())
    _assert_points_identical(res_on.points, ref.points)
    # resume=False clears telemetry artifacts too
    CampaignJob(spec_on, root=root, job_id="t", resume=False)
    for key in job_on.cells:
        assert job_on.cell_telemetry(key) is None
    assert not os.path.exists(job_on.metrics_path)


def test_status_is_live_and_safe_during_background_run(tmp_path):
    """status() concurrent with start(): monotone done counts, in_flight
    visibility, and no torn reads; errors surface in both wait() and
    status()."""
    import time as time_mod

    spec = _spec()
    job = CampaignJob(spec, root=str(tmp_path), job_id="bg")
    seen_done = []
    seen_flight = set()
    job.start()
    while True:
        st = job.status()
        assert 0 <= st.done_cells <= st.total_cells
        seen_done.append(st.done_cells)
        if st.in_flight is not None:
            seen_flight.add(st.in_flight)
        assert st.error is None
        if not st.running:
            break
        time_mod.sleep(0.01)
    final = job.wait()
    assert final.complete and final.done_cells == len(job.cells)
    assert seen_done == sorted(seen_done), "done count went backwards"
    assert seen_flight <= {k.slug for k in job.cells}
    # a second start() after completion is well-defined (no-op run)
    job.start()
    assert job.wait().complete

    # error path: a persistently failing cell is isolated — the run
    # loop exhausts its retry budget, records cell_error, and the job
    # finishes (incomplete, not crashed); wait() does NOT re-raise
    boom = CampaignJob(_spec(rates=(0.2,)), root=str(tmp_path),
                       job_id="boom", max_retries=0)

    def explode(key, checkpoint=None):
        raise RuntimeError("cell exploded")

    boom.executor.run_cell = explode
    boom.start()
    st = boom.wait()
    assert not st.running and not st.complete
    assert st.done_cells == 0
    errs = [r for r in _metrics(boom) if r["event"] == "cell_error"]
    assert len(errs) == len(boom.cells)
    assert all("cell exploded" in r["error"] for r in errs)

    # run()-level failures (not cell execution) still re-raise
    crash = CampaignJob(_spec(rates=(0.2,)), root=str(tmp_path),
                        job_id="crash")
    crash._run_cell_with_retry = None      # type: ignore[assignment]
    crash.start()
    with pytest.raises(TypeError):
        crash.wait()
    st = crash.status()
    assert st.error is not None and not st.running


# ------------------------------------------------------------------ #
# chaos hardening: corrupt checkpoints, poisoned cells
# ------------------------------------------------------------------ #
def test_corrupt_cell_npz_quarantined_and_recomputed(tmp_path):
    """Truncate a completed cell's npz: the resume must detect it via
    the sha256 sidecar, move it to cells/quarantine/, record the event,
    recompute the cell, and still emit a byte-identical CSV."""
    import os

    spec = _spec()
    root = str(tmp_path)
    res, job = run_campaign_service(spec, root=root, job_id="q")
    with open(job.csv_path, "rb") as f:
        ref_csv = f.read()
    victim = job.cells[1]
    path = job._cell_path(victim)
    with open(path, "rb") as f:
        blob = f.read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 2])

    res2, job2 = run_campaign_service(spec, root=root, job_id="q")
    assert res2 is not None
    m = _metrics(job2)
    quar = [r for r in m if r["event"] == "cell_quarantined"]
    assert [r["cell"] for r in quar] == [victim.slug]
    assert os.path.exists(
        os.path.join(job2.quarantine_dir, f"{victim.slug}.npz"))
    # the recomputed cell re-verifies; results and CSV are unchanged
    with open(job2.csv_path, "rb") as f:
        assert f.read() == ref_csv
    _assert_points_identical(res.points, res2.points)
    # a third run is clean: no quarantine events, everything cached
    res3, job3 = run_campaign_service(spec, root=root, job_id="q")
    m3 = _metrics(job3)
    assert not [r for r in m3 if r["event"] == "cell_quarantined"]
    assert all(r["cached"] for r in m3 if r["event"] == "cell")


def test_poisoned_cell_is_isolated_and_resume_completes(tmp_path):
    """One persistently failing cell: bounded retries with the error in
    metrics.jsonl, every other cell completes, and an un-poisoned
    resume finishes the job byte-identically to a clean reference."""
    spec = _spec()
    root = str(tmp_path)
    _, ref_job = run_campaign_service(spec, root=root, job_id="ref")

    job = CampaignJob(spec, root=root, job_id="p", max_retries=1,
                      retry_backoff_s=0.0)
    victim = job.cells[0].slug
    real = job.executor.run_cell

    def flaky(key, checkpoint=None):
        if key.slug == victim:
            raise RuntimeError("poisoned cell")
        return real(key, checkpoint=checkpoint)

    job.executor.run_cell = flaky
    assert job.run() is False             # incomplete, not crashed
    m = _metrics(job)
    retries = [r for r in m if r["event"] == "cell_retry"]
    assert len(retries) == 2              # max_retries + 1 attempts
    assert all(r["cell"] == victim and "poisoned" in r["error"]
               for r in retries)
    errs = [r for r in m if r["event"] == "cell_error"]
    assert [r["cell"] for r in errs] == [victim]
    assert m[-1]["event"] == "job_done"
    assert m[-1]["failed"] == 1
    assert m[-1]["done"] == len(job.cells) - 1
    done = {k.slug for k in job.completed_cells()}
    assert done == {k.slug for k in job.cells} - {victim}

    res, job2 = run_campaign_service(spec, root=root, job_id="p")
    assert res is not None
    with open(job2.csv_path, "rb") as a, \
            open(ref_job.csv_path, "rb") as b:
        assert a.read() == b.read()


def test_cell_checkpoint_corruption_sets_aside_and_restarts(tmp_path):
    """A mid-cell snapshot that fails its sha256 (or fails to parse) is
    *no checkpoint*: set aside as .corrupt, load() returns None, and the
    cell restarts from cycle 0 — slower, never wrong."""
    import os

    ck = CellCheckpoint(str(tmp_path / "c.npz"))
    ck.save({"a": np.arange(3)}, {"cycle": 7})
    assert os.path.exists(ck.path + ".sha256")
    arrays, meta = ck.load()
    assert meta == {"cycle": 7} and np.array_equal(arrays["a"],
                                                   np.arange(3))
    with open(ck.path, "r+b") as f:
        f.write(b"xx")
    assert ck.load() is None
    assert os.path.exists(ck.path + ".corrupt")
    assert not os.path.exists(ck.path)
    assert not os.path.exists(ck.path + ".sha256")
    assert ck.load() is None              # stays gone
    ck.clear()                            # idempotent on the empty state


def test_job_trace_records_cells_and_is_perfetto_parseable(tmp_path):
    from repro.obs.trace import read_trace, validate_events

    spec = _spec(base=BASE.replace(telemetry=True, tel_slots=6))
    res, job = run_campaign_service(spec, root=str(tmp_path),
                                    job_id="tr", trace=True)
    assert res is not None
    events = read_trace(job.trace_path)
    assert validate_events(events) == []
    names = [e["name"] for e in events]
    # one cell span per cell, and the scenario cells' ctrl-plane chain
    assert names.count("cell") == len(job.cells)
    assert "LinkFail" in names and "replan" in names
    assert "build_plans_batched" in names
    slugs = {e["args"]["slug"] for e in events if e["name"] == "cell"}
    assert slugs == {k.slug for k in job.cells}


class _SpanList:
    """A tracer kept in memory: the complete spans it was handed."""

    enabled = True

    def __init__(self):
        self.spans = []

    def now_us(self):
        import time
        return time.time() * 1e6

    def complete(self, name, ts_us, dur_us, *, args=None, **kw):
        self.spans.append(dict(name=name, ts=ts_us, dur=dur_us,
                               args=args or {}))

    def instant(self, name, **kw):
        pass

    def counter(self, name, values, **kw):
        pass

    def flush(self):
        pass


def test_service_takes_a_tracer_from_outside(tmp_path):
    spec = _spec(scenarios=(), chunk=400)
    with pytest.raises(ValueError):
        CampaignJob(spec, root=str(tmp_path), job_id="both", trace=True,
                    tracer=_SpanList())
    tr = _SpanList()
    res, job = run_campaign_service(spec, root=str(tmp_path), job_id="out",
                                    tracer=tr)
    assert res is not None and not os.path.exists(job.trace_path)
    by = {}
    for s in tr.spans:
        by.setdefault(s["name"], []).append(s)
    (opened,) = by["job_open"]
    assert opened["args"] == {"job": "out"} and opened["dur"] >= 0
    slugs = [k.slug for k in job.cells]
    for name in ("prep_topo", "cell", "cell_save"):
        assert [s["args"]["slug"] for s in by[name]] == slugs
    # the first cell builds the topology's plans, the second finds them
    assert [s["args"]["cached"] for s in by["prep_topo"]] == [False, True]
    for prep, cell, save in zip(by["prep_topo"], by["cell"],
                                by["cell_save"]):
        assert opened["ts"] + opened["dur"] <= prep["ts"]
        assert prep["ts"] + prep["dur"] <= cell["ts"]
        assert cell["ts"] + cell["dur"] <= save["ts"]
        # 1200 cycles in chunks of 400, inside the cell
        chunks = [c for c in by["chunk"]
                  if c["args"]["slug"] == cell["args"]["slug"]]
        assert [c["args"]["cycles"] for c in chunks] == [400] * 3
        assert all(cell["ts"] <= c["ts"] and c["ts"] + c["dur"]
                   <= cell["ts"] + cell["dur"] for c in chunks)
    # a second job reads the plans from the shared plan cache
    again = _SpanList()
    run_campaign_service(spec, root=str(tmp_path), job_id="again",
                         tracer=again)
    assert [s["args"]["cached"] for s in again.spans
            if s["name"] == "prep_topo"] == [True, True]
