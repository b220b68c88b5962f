"""Drive the system under test with the generator's jobs.

Everything here calls the program's public entry points: the campaign
service (``run_campaign_service``) and the control plane
(``run_controlled``).  The program is imported lazily, after the
harness has found the chip.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import time

import numpy as np


def program_topology(config: dict):
    from repro.core import mesh2d, torus
    if config["fabric"] == "mesh":
        return mesh2d(*config["dims"])
    return torus(*config["dims"])


def sim_config(config: dict, algo: str):
    from repro.noc import Algo, SimConfig
    return SimConfig(algo=Algo[algo], num_vcs=config["num_vcs"],
                     buf_per_vc=config["buf_per_vc"],
                     packet_len=config["packet_len"],
                     src_queue_pkts=config["src_queue_pkts"],
                     cycles=config["cycles"], warmup=config["warmup"],
                     drain=config["drain"])


def scenario(job: dict, config: dict, mix: dict, name: str):
    """The job's storm as the program's event schedule."""
    from repro.noc import (LinkFail, LinkRecover, ReplanConfig, Scenario,
                           TrafficDrift)
    evs = []
    for e in job["events"]:
        if e.kind == "fail":
            evs.append(LinkFail(cycle=e.cycle, links=e.links))
        elif e.kind == "recover":
            evs.append(LinkRecover(cycle=e.cycle, links=e.links))
        else:
            evs.append(TrafficDrift(cycle=e.cycle, traffic=e.traffic))
    rc = ReplanConfig(epoch=config["epoch"], **config["replan"])
    return Scenario(name, events=tuple(evs), policy=mix["policy"],
                    replan=rc)


# --------------------------------------------------------------------- #
# what the control plane hands between its stages
# --------------------------------------------------------------------- #
class Recorder:
    """Records, per control-plane session, what a replan took and gave:
    its inputs (the estimated matrix and the link bandwidths), the
    planner's BiDOR table and the table that shipped.  It wraps the two
    entry points as the control plane's module sees them, for the length
    of a ``with`` block, and copies small arrays only; nothing it does
    changes a result.
    """

    def __init__(self):
        self.sessions: list[list[dict]] = []
        self.open: dict | None = None   # the replan in progress

    def new_session(self) -> list[dict]:
        self.sessions.append([])
        return self.sessions[-1]

    @contextlib.contextmanager
    def attached(self):
        from repro.noc import ctrl
        orig = {k: getattr(ctrl, k) for k in ("replan", "build_plan_fast")}
        log = self

        def replan(topo, traffic, channel_bw, prev=None, **kw):
            rec = dict(kind="replan",
                       traffic=np.array(traffic, np.float64),
                       bw=np.array(channel_bw, np.float64))
            log.sessions[-1].append(rec)
            log.open = rec
            try:
                table, nr = orig["replan"](topo, traffic, channel_bw,
                                           prev, **kw)
            finally:
                log.open = None
            rec["shipped"] = np.array(table.choice, np.int8)
            return table, nr

        def build_plan_fast(topo, traffic, **kw):
            plan = orig["build_plan_fast"](topo, traffic, **kw)
            rec = dict(choice=np.array(plan.table.choice, np.int8),
                       unroutable=(None if plan.table.unroutable is None
                                   else np.array(plan.table.unroutable)))
            if log.open is None:
                log.sessions[-1].append(dict(
                    kind="seed", traffic=np.array(traffic, np.float64),
                    plan=rec))
            else:
                log.open["plan"] = rec
            return plan

        try:
            ctrl.replan = replan
            ctrl.build_plan_fast = build_plan_fast
            yield self
        finally:
            for k, v in orig.items():
                setattr(ctrl, k, v)


class SpanLog:
    """In-memory tracer with the program's tracer interface.  ``keep``
    names the complete spans it stores (None keeps every span); instants
    and counters are dropped."""

    enabled = True

    def __init__(self, keep=None):
        self.keep = None if keep is None else frozenset(keep)
        self.spans: list[dict] = []

    def now_us(self) -> float:
        return time.time() * 1e6

    def complete(self, name, ts_us, dur_us, *, cat="host", args=None,
                 tid=0):
        if self.keep is None or name in self.keep:
            self.spans.append(dict(name=name, ts=float(ts_us),
                                   dur=float(dur_us), args=args or {}))

    def instant(self, name, **kw):
        pass

    def counter(self, name, values, **kw):
        pass

    @contextlib.contextmanager
    def span(self, name, *, cat="host", args=None, tid=0):
        t0 = self.now_us()
        try:
            yield {}
        finally:
            self.complete(name, t0, self.now_us() - t0, args=args)

    def flush(self):
        pass

    def close(self):
        pass


# --------------------------------------------------------------------- #
# services
# --------------------------------------------------------------------- #
class Service:
    """Runs jobs of one cell through the program and keeps what each
    job returned for the comparison."""

    def __init__(self, config: dict, mix: dict, workdir: str, tracer,
                 traced: bool = False):
        self.config, self.mix = config, mix
        self.topo = program_topology(config)
        self.workdir = workdir
        self.tracer = tracer
        self.traced = traced
        self.recorder = Recorder()
        if os.path.exists(workdir):
            shutil.rmtree(workdir)
        os.makedirs(workdir)
        self.outputs: list[dict] = []

    def run(self, job: dict) -> dict:
        """One job; returns its record (host wall, lanes, cycles,
        per-lane results)."""
        t0 = time.perf_counter()
        ts = self.tracer.now_us()
        if job["service"] == "campaign":
            out = self._campaign(job)
        else:
            out = self._session(job)
        out["wall_s"] = time.perf_counter() - t0
        self.tracer.complete("job", ts, self.tracer.now_us() - ts,
                             args={"index": job["index"]})
        out.update(index=job["index"], job=job)
        self.outputs.append(out)
        return out

    def _campaign(self, job: dict) -> dict:
        from repro.noc import CampaignSpec, run_campaign_service
        cfg = sim_config(self.config, self.mix["algo"])
        spec = CampaignSpec(
            topo=self.topo, algos=(cfg.algo,),
            patterns=((self.mix["pattern"], job["traffic"]),),
            rates=tuple(job["rates"]), seeds=tuple(job["seeds"]),
            base=cfg, chunk=self.config["chunk"])
        res, cjob = run_campaign_service(
            spec, root=os.path.join(self.workdir, "jobs"),
            plan_cache=os.path.join(self.workdir, "plan-cache"),
            resume=False, trace=self.traced)
        if self.traced:
            for ev in read_spans(cjob.trace_path):
                self.tracer.complete(ev["name"], ev["ts"], ev["dur"],
                                     args=ev.get("args"))
        cells = [] if res is None else list(res.wall_clock_s.values())
        return dict(results=[] if res is None else
                    [p.result for p in res.points],
                    cells_wall_s=cells, complete=res is not None,
                    lanes=spec.num_points, cycles=cfg.cycles)

    def _session(self, job: dict) -> dict:
        from repro.noc import run_controlled
        cfg = sim_config(self.config, self.config["algo"])
        scen = scenario(job, self.config, self.mix, f"storm{job['index']}")
        rec = self.recorder.new_session()
        with self.recorder.attached():
            res = run_controlled(self.topo, job["traffic"], cfg, scen,
                                 rates=job["rates"], seeds=job["seeds"],
                                 tracer=self.tracer)
        replans = [dataclasses.asdict(r) for r in res.replans]
        return dict(results=list(res.results), replans=replans,
                    stages=rec, complete=True,
                    lanes=len(res.points), cycles=cfg.cycles)

    def seed_plan(self):
        """The BiDOR table the campaign's jobs ran (from the service's
        plan cache, where the warm-up job put it); None for XY."""
        if self.mix["service"] != "campaign" or self.mix["algo"] != "BIDOR":
            return None
        from repro.core.plan_cache import PlanCache
        from repro.core.plan_fast import plan_cache_key
        tm = self.outputs[0]["job"]["traffic"] if self.outputs else None
        cache = PlanCache(os.path.join(self.workdir, "plan-cache"))
        plan = cache.get(plan_cache_key(self.topo, tm), self.topo)
        return None if plan is None else np.asarray(plan.table.choice)


def read_spans(path: str) -> list[dict]:
    """Complete spans of a trace-event JSON stream (possibly
    unterminated)."""
    import json
    if not os.path.exists(path):
        return []
    with open(path) as f:
        body = f.read().strip().lstrip("[").rstrip().rstrip("]").rstrip()
    body = body.rstrip(",")
    events = json.loads("[" + body + "]") if body else []
    return [e for e in events if e.get("ph") == "X"]
