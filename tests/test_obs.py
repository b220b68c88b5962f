"""Flight recorder: trace-writer schema, structured logging, in-sim
telemetry probes (bit-identity off AND on, fused/unfused/Pallas parity),
ctrl-plane tracing, and the probes-reproduce-the-dynamics-gap check."""

import json

import numpy as np
import pytest

from repro.core import build_plan, mesh2d, traffic
from repro.kernels import simstep
from repro.noc import (Algo, LinkFail, ReplanConfig, Scenario, SimConfig,
                       run_controlled)
from repro.noc.sim import (build_tables, fresh_state,
                           run_sim, run_sweep, static_bw_slots)
from repro.obs import (EventLog, TEL_COUNT_FIELDS, TEL_KEYS, Telemetry,
                       TraceWriter, read_trace, resolved_epoch,
                       telemetry_state, validate_events)
from repro.obs.trace import span

TOPO = mesh2d(3, 3)
UNI = traffic.uniform(TOPO)
CFG = SimConfig(cycles=400, warmup=100, drain=50, injection_rate=0.2)

SCALAR_FIELDS = ("injected_flits", "ejected_flits", "in_flight_flits",
                 "reorder_value", "meas_cycles", "saturated",
                 "avg_latency", "max_latency", "throughput", "offered",
                 "lcv", "p50_latency", "p90_latency", "p99_latency",
                 "link_load_max")


# ------------------------------------------------------------------ #
# trace writer
# ------------------------------------------------------------------ #
def test_trace_writer_roundtrip_schema_and_kill_safety(tmp_path):
    path = str(tmp_path / "t" / "trace.jsonl")
    w = TraceWriter(path)
    w.instant("drift_detected", cat="ctrl", args={"cycle": 100})
    w.counter("drift_tv", {"tv": 0.12}, cat="ctrl")
    t0 = w.now_us()
    w.complete("replan", t0, 1234.5, cat="ctrl", args={"trigger": "fault"})
    with span(w, "build", cat="plan", nodes=9):
        pass
    with pytest.raises(RuntimeError):
        with span(w, "boom", cat="plan"):
            raise RuntimeError("x")
    # NO close(): the stream must parse as written (kill safety)
    events = read_trace(path)
    assert [e["name"] for e in events] == [
        "drift_detected", "drift_tv", "replan", "build", "boom"]
    assert validate_events(events) == []
    assert events[2]["dur"] == 1234.5
    assert events[4]["args"]["error"] is True
    # Chrome trace-event JSON Array Format: Perfetto accepts the raw
    # file with the unterminated array closed
    raw = open(path).read()
    assert raw.startswith("[\n")
    parsed = json.loads(raw.rstrip().rstrip(",") + "]")
    assert len(parsed) == len(events)
    # appending (a resumed job) keeps the stream one valid array
    w2 = TraceWriter(path)
    w2.instant("resumed", cat="log")
    assert [e["name"] for e in read_trace(path)][-1] == "resumed"

    problems = validate_events([{"ph": "X", "ts": 1, "pid": "p"}])
    assert problems, "missing name/dur must be reported"


def test_event_log_quiet_verbose_and_trace_forwarding(tmp_path, capsys):
    quiet = EventLog(verbose=False)
    quiet.event("replan", "should not print", cycle=1)
    assert capsys.readouterr().out == ""

    path = str(tmp_path / "trace.jsonl")
    w = TraceWriter(path)
    loud = EventLog(verbose=True, tracer=w)
    loud.event("replan", "ctrl[x] replan @ 100", cycle=100)
    loud.event("cell_done", cell="c0", wall_s=1.5)   # default message
    out = capsys.readouterr().out
    assert "ctrl[x] replan @ 100" in out
    assert "cell_done" in out and "cell=c0" in out
    events = read_trace(path)
    assert [e["name"] for e in events] == ["replan", "cell_done"]
    assert events[0]["args"]["cycle"] == 100


# ------------------------------------------------------------------ #
# telemetry probes
# ------------------------------------------------------------------ #
def test_telemetry_state_shapes_and_epoch_resolution():
    cfg = CFG.replace(telemetry=True, tel_slots=8)
    tables, meta = build_tables(TOPO, UNI, None, cfg.num_vcs)
    st = telemetry_state(meta, cfg)
    assert set(st) == set(TEL_KEYS)
    assert st["tel_chan"].shape == (8, meta["C"])
    assert st["tel_counts"].shape == (8, len(TEL_COUNT_FIELDS))
    # auto epoch covers the whole run: ceil(400 / 8) = 50
    assert resolved_epoch(cfg) == 50
    assert resolved_epoch(cfg.replace(tel_epoch=25)) == 25
    assert resolved_epoch(cfg.replace(telemetry=False)) == 0
    # off -> no telemetry keys in the state pytree at all
    off = fresh_state(meta, CFG)
    assert not any(k in off for k in TEL_KEYS)


def test_telemetry_off_on_bit_identity_and_fused_unfused_parity():
    """Switching probes on must not move a single bit of the core
    statistics, on either per-cycle path; the probe arrays themselves
    must agree bit-for-bit between the fused and unfused paths."""
    plan = build_plan(TOPO, UNI)
    tels = {}
    for uk in (False, True):
        cfg = CFG.replace(algo=Algo.BIDOR, use_kernel=uk)
        off = run_sweep(TOPO, UNI, cfg, [0.1, 0.2], plan.table,
                        seeds=[0])
        on, tel = run_sweep(TOPO, UNI,
                            cfg.replace(telemetry=True, tel_slots=8),
                            [0.1, 0.2], plan.table, seeds=[0],
                            return_telemetry=True)
        for a, b in zip(off, on):
            for f in SCALAR_FIELDS:
                assert getattr(a, f) == getattr(b, f), (uk, f)
            assert np.array_equal(a.node_load, b.node_load)
        assert tel is not None
        tels[uk] = tel
    for arr in ("chan", "counts", "cycles", "lat", "qocc"):
        assert np.array_equal(getattr(tels[False], arr),
                              getattr(tels[True], arr)), arr


def test_telemetry_content_invariants_and_accessors():
    cfg = CFG.replace(telemetry=True, tel_slots=8)
    res, tel = run_sim(TOPO, UNI, cfg, return_telemetry=True)
    assert tel.num_lanes == 1 and tel.num_slots == 8
    # every cycle lands in exactly one slot
    assert tel.cycles.sum() == cfg.cycles
    assert np.array_equal(tel.active_slots(), np.arange(8))
    offered, accepted = tel.count("offered"), tel.count("accepted")
    shed, delivered = tel.count("shed"), tel.count("delivered")
    assert (accepted <= offered).all()
    assert np.array_equal(shed, offered - accepted)
    assert delivered.sum() <= accepted.sum()
    assert delivered.sum() > 0, "nothing delivered in 400 cycles?"
    # per-slot latency histograms: one tail per delivered packet, minus
    # any beyond the histogram range (mode='drop')
    assert tel.lat.sum() <= delivered.sum()
    assert tel.latency_percentile(0.5).shape == (1, 8)
    occ = tel.occupancy_mean()
    assert ((0 <= occ) & (occ <= 1)).all()
    # static bw normalization: loads are finite, dead-free, plausible
    tel = tel.with_bw(static_bw_slots(TOPO, cfg))
    peak = tel.peak_link_load()
    assert peak.shape == (1, 8)
    assert (peak >= 0).all() and np.isfinite(peak).all()
    assert peak.max() <= 1.5, "normalized link load implausibly high"

    # save/load round-trip
    import os
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "tel.npz")
        tel.save(p)
        back = Telemetry.load(p)
    assert back.epoch_len == tel.epoch_len
    for arr in ("chan", "counts", "cycles", "lat", "qocc", "bw"):
        assert np.array_equal(getattr(back, arr), getattr(tel, arr)), arr


def test_pallas_interpret_parity_includes_telemetry():
    """The generic Pallas kernel carries the probe rings through
    untouched: interpret-mode fused step == unfused oracle on every
    state array, telemetry included."""
    import jax

    cfg = CFG.replace(cycles=60, warmup=0, drain=0, telemetry=True,
                      tel_slots=4, tel_epoch=16)
    tables, meta = build_tables(TOPO, UNI, None, cfg.num_vcs)
    from repro.noc.sim import _make_step
    oracle = _make_step(meta, cfg)
    fused = simstep.make_step(meta, cfg, use_pallas=True, interpret=True)
    s_a = fresh_state(meta, cfg)
    s_b = {k: v.copy() for k, v in s_a.items()}
    for cyc in range(20):
        s_a, _ = oracle(tables, s_a, cyc)
        s_b, _ = fused(tables, s_b, cyc)
    s_a, s_b = jax.device_get(s_a), jax.device_get(s_b)
    for k in s_a:
        assert np.array_equal(s_a[k], s_b[k]), k
    assert s_a["tel_cycles"].sum() == 20


# ------------------------------------------------------------------ #
# controlled runs: ctrl-plane tracing + fault-aware bw timeline
# ------------------------------------------------------------------ #
LINK01 = ((0, 1), (1, 0))


def _linkfail_run(policy: str, tracer=None):
    cfg = SimConfig(algo=Algo.BIDOR, cycles=1200, warmup=200, drain=200,
                    injection_rate=0.25, telemetry=True, tel_slots=12)
    scen = Scenario("fail", events=(LinkFail(400, LINK01),),
                    policy=policy, replan=ReplanConfig(epoch=200))
    tm = traffic.transpose(TOPO)
    plan = build_plan(TOPO, tm)
    return run_controlled(TOPO, tm, cfg, scen, rates=[0.25], seeds=[0],
                          bidor_table=plan.table, nrank0=plan.nrank,
                          tracer=tracer)


def test_run_controlled_trace_events_and_bw_timeline(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    res = _linkfail_run("online", tracer=TraceWriter(path))
    events = read_trace(path)
    assert validate_events(events) == []
    names = [e["name"] for e in events]
    assert "LinkFail" in names and "epoch" in names
    assert "replan" in names and "hot_swap" in names
    # the replan span carries the decision context and real wall time
    (rp,) = [e for e in events if e["name"] == "replan"]
    assert rp["ph"] == "X" and rp["dur"] > 0
    assert rp["args"]["trigger"] == "fault"
    assert rp["args"]["iterations"] >= 1
    # chronology: the fault instant precedes its replan span's end
    (lf,) = [e for e in events if e["name"] == "LinkFail"]
    assert lf["ts"] <= rp["ts"] + rp["dur"]

    # telemetry attached, with the fault-aware bw timeline: slots before
    # the failure normalize by full bw, slots after by the degraded bw
    tel = res.telemetry
    assert tel is not None and tel.bw is not None
    c01 = TOPO.channel_index(0, 1)
    starts = tel.slot_starts()
    assert (tel.bw[starts < 400, c01] > 0).all()
    assert (tel.bw[starts >= 400, c01] == 0).all()
    # dead-channel convention: failed link contributes zero load
    assert (tel.link_load()[:, starts >= 400, c01] == 0).all()


def test_probes_reproduce_online_vs_stale_gap():
    """The acceptance check: from the in-sim probe rings ALONE, the
    online policy's post-replan peak-link-load trajectory must drop
    below the stale policy's (pinned at the saturated degraded link)."""
    stale = _linkfail_run("stale").telemetry
    online = _linkfail_run("online").telemetry
    starts = stale.slot_starts()
    post = [int(s) for s in stale.active_slots() if starts[s] >= 600]
    assert post
    g_stale = float(stale.peak_link_load()[0][post].mean())
    g_online = float(online.peak_link_load()[0][post].mean())
    assert g_online < g_stale, (g_online, g_stale)


def test_run_controlled_without_tracer_is_unchanged():
    """tracer=None (the default) must leave results identical to the
    traced run — tracing is observation, never behavior."""
    import dataclasses
    a = _linkfail_run("online")
    b = _linkfail_run("online", tracer=None)
    assert [dataclasses.astuple(x) for x in a.replans] \
        == [dataclasses.astuple(x) for x in b.replans]
    for ra, rb in zip(a.results, b.results):
        for f in SCALAR_FIELDS:
            assert getattr(ra, f) == getattr(rb, f), f
    for arr in ("chan", "counts", "cycles", "lat", "qocc", "bw"):
        assert np.array_equal(getattr(a.telemetry, arr),
                              getattr(b.telemetry, arr)), arr
