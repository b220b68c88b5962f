"""Plain reference of one controlled session of the quasi-static control
plane (online policy, BiDOR), replayed with the tables the program
shipped.

It runs the reference simulator (:mod:`.sim`) through the same control
epochs and events, keeps its own traffic estimate (an exponential moving
average of the per-flow packet counts) and its own drift detector (total
variation between per-channel forwarding profiles), and decides by
itself when to replan, with which trigger, and whether a replan sheds
too many pairs to be installed.  At each replan it installs the table
the program shipped — so a choice broken the other way at a near-tie
does not carry into everything after it — and returns, for every replan,
the inputs it planned from, so that each table can be compared with the
reference planner on its own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import sim as rsim
from .grid import Grid


class Estimator:
    def __init__(self, n: int, ema: float, prior):
        self.ema, self.n = float(ema), n
        self.m = None
        self.prior = np.asarray(prior, np.float64).copy()

    def update(self, counts):
        c = np.asarray(counts, np.float64)
        tot = c.sum()
        if tot <= 0:
            return
        obs = c / tot
        self.m = obs if self.m is None else \
            (1.0 - self.ema) * self.m + self.ema * obs

    @property
    def matrix(self):
        m = (self.m if self.m is not None else self.prior).copy()
        np.fill_diagonal(m, 0.0)
        s = m.sum()
        return m / s if s > 0 else None


class Detector:
    def __init__(self, threshold: float):
        self.threshold = float(threshold)
        self.ref = None
        self.last = 0.0

    def reset(self):
        self.ref, self.last = None, 0.0

    def update(self, counts) -> bool:
        c = np.asarray(counts, np.float64)
        tot = c.sum()
        if tot <= 0:
            return False
        prof = c / tot
        if self.ref is None:
            self.ref = prof
            return False
        self.last = 0.5 * float(np.abs(prof - self.ref).sum())
        return self.last > self.threshold


def replay(grid: Grid, traffic, sim: dict, replan_cfg: dict, epoch: int,
           events, points, seed_choice, shipped: list) -> dict:
    """Replay one session.

    ``events`` are the generator's events; ``seed_choice`` the seed
    plan's table and ``shipped`` the tables the program's replans
    returned, in order.  Returns the lane statistics, the schedule
    (every replan the reference decided, installed or rejected) and the
    inputs of each replan.
    """
    channels = grid.channels()
    chan_index = {(int(u), int(v)): i for i, (u, v) in enumerate(channels)}
    tables, meta = rsim.build_tables(grid, traffic, seed_choice,
                                     num_vcs=sim["num_vcs"])
    states = rsim.make_states(meta, sim, points)
    rates = [r for r, _ in points]
    base_bw = np.ones(len(channels))
    bw = base_bw.copy()
    cur_traffic = np.asarray(traffic, np.float64)
    fault_pending, cur_unroutable = False, None
    est = Estimator(grid.n, replan_cfg["ema"], traffic)
    det = Detector(replan_cfg["drift_threshold"])
    total = sim["cycles"]
    bounds = sorted(set(range(epoch, total, epoch)) | {total}
                    | {e.cycle for e in events if 0 < e.cycle < total})
    nl = len(points)
    prev_seq = np.zeros((nl, grid.n, grid.n), np.int64)
    prev_seen = np.zeros((nl, len(channels)), np.int64)
    sat = np.zeros(nl, bool)
    schedule, inputs = [], []
    it = iter(shipped)
    t0 = 0
    for t1 in bounds:
        states = rsim.advance(tables, states, meta, sim, t1 - t0)
        t0 = t1
        seq = np.asarray(jax.device_get(states["next_seq"]), np.int64)
        seen = np.asarray(jax.device_get(states["chan_seen"]), np.int64)
        d_seq, d_seen = seq - prev_seq, seen - prev_seen
        prev_seq, prev_seen = seq, seen
        if t1 > sim["warmup"]:
            sat |= rsim.occupancy(jax.device_get(states["q_size"]),
                                  tables.p_gen, sim["src_queue_pkts"]) \
                >= replan_cfg["sat_occupancy"]
        est.update(d_seq.sum(0))
        drifted = det.update(d_seen.sum(0))
        if t1 >= total:
            break
        due = [e for e in events if e.cycle == t1]
        if due:
            new_traffic, fault = None, False
            for e in due:
                if e.kind == "drift":
                    new_traffic = np.asarray(e.traffic, np.float64)
                else:
                    ids = [chan_index[tuple(map(int, lk))] for lk in e.links]
                    bw = bw.copy()
                    bw[ids] = 0.0 if e.kind == "fail" else base_bw[ids]
                    fault = True
            gen = new_traffic
            if new_traffic is not None and cur_unroutable is not None:
                gen = np.where(cur_unroutable, 0.0, new_traffic)
            tables = rsim.retarget(tables, traffic=gen,
                                   bw=bw if fault else None)
            if new_traffic is not None:
                cur_traffic = new_traffic
                states["rate"] = jnp.asarray(rates, jnp.float32)
            fault_pending |= fault
        trigger = "fault" if fault_pending else "drift"
        m = est.matrix
        if not (fault_pending or drifted) or m is None:
            continue
        choice = next(it, None)
        entry = dict(cycle=t1, trigger=trigger, drift_distance=det.last)
        inputs.append(dict(traffic=m, bw=bw.copy()))
        schedule.append(entry)
        if choice is None:          # the program made fewer replans
            entry["missing"] = True
            break
        unr = unroutable(grid, channels, bw)
        if unr is not None:
            demanded = cur_traffic > 0
            n_dem = int(demanded.sum())
            shed = int((unr & demanded).sum()) / n_dem if n_dem else 0.0
            if shed > replan_cfg["max_shed"]:
                entry["rejected"] = True
                det.reset()
                fault_pending = False
                continue
        gen = cur_traffic
        cur_unroutable = None
        if unr is not None and unr.any():
            cur_unroutable = unr
            gen = np.where(unr, 0.0, cur_traffic)
        entry["unroutable_pairs"] = 0 if unr is None else int(unr.sum())
        tables = rsim.retarget(tables, choice=choice, traffic=gen)
        det.reset()
        fault_pending = False
    host = jax.device_get(states)
    stats = [rsim.statistics(jax.tree.map(lambda x: x[i], host), sim,
                             base_bw, saturated=bool(sat[i]))
             for i in range(nl)]
    return dict(stats=stats, schedule=schedule, inputs=inputs,
                extra_shipped=sum(1 for _ in it))


def unroutable(grid: Grid, channels, bw):
    """(N, N) pairs that neither order can route around the failed
    links; None on an intact fabric."""
    dead = bw <= 0
    if not dead.any():
        return None
    n = grid.n
    down = np.zeros((n, n), bool)
    down[channels[dead, 0], channels[dead, 1]] = True
    ok_any = np.zeros((n, n), bool)
    for o in grid.orders:
        seq = grid.walk(o)
        ok = np.ones((n, n), bool)
        for h in range(seq.shape[-1] - 1):
            a, b = seq[..., h], seq[..., h + 1]
            ok &= ~((a != b) & down[a, b])
        ok_any |= ok
    out = ~ok_any
    np.fill_diagonal(out, False)
    return out
