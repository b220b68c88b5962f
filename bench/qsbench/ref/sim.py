"""Plain reference of the flit-level simulator, for the routings the
benchmark runs (XY, and BiDOR from a given choice table).

A copy of the program's unfused per-cycle transition (the chain it keeps
as its own differential oracle, ``_make_step`` in ``src/repro/noc/sim.py``
at the commit that added this benchmark), cut down to XY and BiDOR with
telemetry and the stall watchdog off, and fed only tables built here
from :mod:`.grid`.  It imports nothing of the program, so a later change
to the program cannot move it.  The model: input-queued wormhole
routers, ``num_vcs`` VCs per input port, credit flow control, one flit
per channel per cycle, round-robin switch allocation, open-loop
Bernoulli sources.  Every statistic is an integer count, so the program
and this reference agree exactly.

``gen_dtype`` is the precision of the packet-generation tables (float32
as the simulator states them); bfloat16 there is the precision control.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .grid import Grid

NF = 10
(F_SRC, F_DST, F_INTER, F_SEQ, F_TIME,
 F_HOPS, F_ORDER, F_HEAD, F_TAIL, F_PHASE) = range(NF)
NQ = 5
(Q_DST, Q_INTER, Q_ORDER, Q_TIME, Q_SEQ) = range(NQ)
_BIG = 1 << 30


class Tables(NamedTuple):
    port: jnp.ndarray       # (O, N, N) out-port of (order, cur, target)
    choice: jnp.ndarray     # (N, N) order per (s, d)
    neighbor: jnp.ndarray   # (N, P)
    recv_port: jnp.ndarray  # (N, P) input port at the neighbour
    cdf: jnp.ndarray        # (N, N) destination CDF per source
    p_gen: jnp.ndarray      # (N,) generation probability at rate 1
    n_of: jnp.ndarray
    p_of: jnp.ndarray
    v_of: jnp.ndarray
    chan_src_n: jnp.ndarray
    chan_src_p: jnp.ndarray
    chan_of: jnp.ndarray    # (N, P) channel at (node, port); C if none
    chan_bw: jnp.ndarray    # (C,) relative bandwidth (0 = down)


def gen_tables(traffic, dtype=np.float32):
    t = np.asarray(traffic, np.float64)
    row = t.sum(1)
    with np.errstate(invalid="ignore"):
        cdf = np.cumsum(np.where(row[:, None] > 0,
                                 t / np.maximum(row, 1e-300)[:, None], 0), 1)
    p_gen = row * t.shape[0]          # one I/O port per node
    return jnp.asarray(cdf, dtype), jnp.asarray(p_gen, dtype)


def build_tables(grid: Grid, traffic, choice=None, *, num_vcs: int,
                 bw=None, gen_dtype=np.float32) -> tuple[Tables, dict]:
    ch = grid.channels()
    n, p, v = grid.n, grid.num_ports, num_vcs
    c = len(ch)
    cport = grid.channel_ports(ch)
    recv = np.zeros((n, p), np.int32)
    recv[ch[:, 0], cport] = np.where(cport % 2 == 0, cport + 1, cport - 1)
    chan_of = np.full((n, p), c, np.int32)
    chan_of[ch[:, 0], cport] = np.arange(c)
    idx = np.arange(n * p * v)
    cdf, p_gen = gen_tables(traffic, gen_dtype)
    choice = np.zeros((n, n)) if choice is None else choice
    tables = Tables(
        port=jnp.asarray(np.stack([grid.next_port(o, ch)
                                   for o in grid.orders]), jnp.int32),
        choice=jnp.asarray(choice, jnp.int32),
        neighbor=jnp.asarray(grid.neighbors(ch), jnp.int32),
        recv_port=jnp.asarray(recv), cdf=cdf, p_gen=p_gen,
        n_of=jnp.asarray(idx // (p * v), jnp.int32),
        p_of=jnp.asarray((idx // v) % p, jnp.int32),
        v_of=jnp.asarray(idx % v, jnp.int32),
        chan_src_n=jnp.asarray(ch[:, 0], jnp.int32),
        chan_src_p=jnp.asarray(cport, jnp.int32),
        chan_of=jnp.asarray(chan_of),
        chan_bw=jnp.asarray(np.ones(c) if bw is None else bw, jnp.float32))
    meta = dict(N=n, P=p, V=v, NIN=n * p * v, P_LOCAL=grid.port_local,
                O=len(grid.orders), C=c)
    return tables, meta


def retarget(tables: Tables, *, traffic=None, choice=None, bw=None,
             gen_dtype=np.float32) -> Tables:
    kw = {}
    if traffic is not None:
        kw["cdf"], kw["p_gen"] = gen_tables(traffic, gen_dtype)
    if choice is not None:
        kw["choice"] = jnp.asarray(np.asarray(choice, np.int32))
    if bw is not None:
        kw["chan_bw"] = jnp.asarray(np.asarray(bw), jnp.float32)
    return tables._replace(**kw)


def fresh_state(meta: dict, sim: dict, rate: float, seed: int) -> dict:
    n, nin, p, v = meta["N"], meta["NIN"], meta["P"], meta["V"]
    b, q = sim["buf_per_vc"], sim["src_queue_pkts"]
    i32 = jnp.int32
    z = functools.partial(jnp.zeros, dtype=i32)
    rate_bits = int(np.float32(rate).view(np.uint32))
    end = sim["cycles"] - sim["drain"]
    return dict(
        flits=z((nin, b, NF)), fifo_start=z((nin,)), fifo_size=z((nin,)),
        lock_op=jnp.full((nin,), -1, i32), lock_ov=jnp.full((nin,), -1, i32),
        out_held=jnp.full((n, p, v), -1, i32), rr=z((n, p)),
        qpkts=z((n, q, NQ)), q_start=z((n,)), q_size=z((n,)),
        prog=z((n,)), next_seq=z((n, n)), exp_seq=z((n, n)),
        rbits=jnp.zeros((n, n), jnp.uint32),
        node_fwd=z((n,)), eject_flits=z((n,)), chan_fwd=z((meta["C"],)),
        chan_seen=z((meta["C"],)), lat_sum=z(()), lat_cnt=z(()),
        lat_max=z(()), lat_hist=z((sim["lat_bins"],)), reorder_max=z(()),
        injected=z(()), offered=z(()), dropped=z(()), eject_total=z(()),
        meas_cnt=z(()), rate=jnp.float32(rate), cycle0=jnp.int32(0),
        inject_until=jnp.int32(end), measure_until=jnp.int32(end),
        key=jax.random.fold_in(jax.random.PRNGKey(seed), rate_bits))


def make_states(meta, sim, points):
    states = [fresh_state(meta, sim, r, s) for r, s in points]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def make_step(meta: dict, sim: dict):
    """The per-cycle transition (the unfused chain)."""
    bidor = sim["algo"] == "BIDOR"
    n, p, v, nin = meta["N"], meta["P"], meta["V"], meta["NIN"]
    p_local = meta["P_LOCAL"]
    b, q, l = sim["buf_per_vc"], sim["src_queue_pkts"], sim["packet_len"]
    warmup = sim["warmup"]
    lat_bins, lat_w = sim["lat_bins"], sim["lat_bin_width"]
    pv = p * v
    n_ar = jnp.arange(n)
    nin_ar = jnp.arange(nin)

    def fifo_push(state, idx, ok, records):
        slot = (state["fifo_start"][idx] + state["fifo_size"][idx]) % b
        safe = jnp.where(ok, idx, nin)
        state["flits"] = state["flits"].at[safe, slot].set(records,
                                                           mode="drop")
        state["fifo_size"] = state["fifo_size"].at[safe].add(1, mode="drop")
        return state

    def step(t, state, cycle):
        cycle = state["cycle0"] + cycle
        key, kg, kd, km, kv = jax.random.split(state["key"], 5)
        state["key"] = key
        measuring = (cycle >= warmup) & (cycle < state["measure_until"])
        state["meas_cnt"] += measuring.astype(jnp.int32)

        # 1. packet generation (open loop)
        u = jax.random.uniform(kg, (n,))
        gen = (u < (t.p_gen * (state["rate"] / l))) \
            & (cycle < state["inject_until"])
        ud = jax.random.uniform(kd, (n,))
        dst = jnp.clip((t.cdf <= ud[:, None]).sum(1), 0,
                       n - 1).astype(jnp.int32)
        order = (t.choice[n_ar, dst] if bidor
                 else jnp.zeros(n, jnp.int32))
        inter = jnp.full((n,), -1, jnp.int32)
        space = state["q_size"] < q
        push = gen & space
        seq = state["next_seq"][n_ar, dst]
        state["next_seq"] = state["next_seq"] + (
            push[:, None] & (n_ar[None, :] == dst[:, None]))
        slot = (state["q_start"] + state["q_size"]) % q
        row = jnp.where(push, n_ar, n)
        qrec = jnp.stack(
            [dst, inter, order, jnp.full((n,), cycle, jnp.int32), seq], -1)
        state["qpkts"] = state["qpkts"].at[row, slot].set(qrec, mode="drop")
        state["q_size"] = state["q_size"] + push
        state["offered"] += jnp.where(measuring, gen.sum(), 0)
        state["dropped"] += jnp.where(measuring, (gen & ~space).sum(), 0)

        # 2. flit injection (one per node per cycle)
        hs = state["q_start"]
        hpkt = state["qpkts"][n_ar, hs]
        h_dst, h_inter = hpkt[:, Q_DST], hpkt[:, Q_INTER]
        h_order, h_seq, h_time = hpkt[:, Q_ORDER], hpkt[:, Q_SEQ], \
            hpkt[:, Q_TIME]
        fl_head = state["prog"] == 0
        fl_tail = state["prog"] == l - 1
        phase0 = (h_inter < 0) | (h_inter == n_ar)
        vc_in = h_order % v if bidor else (n_ar + h_dst) % v
        lf_idx = (n_ar * p + p_local) * v + vc_in
        can = (state["q_size"] > 0) & (state["fifo_size"][lf_idx] < b)
        inj_rec = jnp.stack(
            [n_ar, h_dst, h_inter, h_seq, h_time, jnp.zeros(n, jnp.int32),
             h_order, fl_head.astype(jnp.int32), fl_tail.astype(jnp.int32),
             phase0.astype(jnp.int32)], -1)
        state = fifo_push(state, lf_idx, can, inj_rec)
        state["prog"] = jnp.where(can, state["prog"] + 1, state["prog"])
        done = can & (state["prog"] >= l)
        state["prog"] = jnp.where(done, 0, state["prog"])
        state["q_start"] = jnp.where(done, (hs + 1) % q, hs)
        state["q_size"] = state["q_size"] - done
        state["injected"] += can.sum()

        # 3. head of line, routing
        st_ = state["fifo_start"]
        g_all = state["flits"][nin_ar, st_]
        g_order = g_all[:, F_ORDER]
        g_head = g_all[:, F_HEAD] != 0
        g_tail = g_all[:, F_TAIL] != 0
        g_inter = g_all[:, F_INTER]
        valid = state["fifo_size"] > 0
        route_phase = (g_all[:, F_PHASE] != 0) | (g_inter < 0) \
            | (g_inter == t.n_of)
        target = jnp.where(route_phase, g_all[:, F_DST], g_inter)
        target = jnp.clip(target, 0, n - 1)
        at_dest = target == t.n_of
        locked = state["lock_op"] >= 0
        eff_order = g_order if bidor else jnp.zeros(nin, jnp.int32)
        op_route = t.port[eff_order, t.n_of, target]
        ov_route = g_order % v if bidor else t.v_of
        op = jnp.where(at_dest, p_local, op_route)
        ov = jnp.where(at_dest, 0, ov_route)
        op = jnp.where(locked, state["lock_op"], op)
        ov = jnp.where(locked, state["lock_ov"], ov)

        # 4. eligibility: credit, free VC, live channel
        opc = jnp.clip(op, 0, p - 1)
        is_eject = op == p_local
        nei = t.neighbor[t.n_of, opc]
        rp = t.recv_port[t.n_of, opc]
        recv_idx = (nei * p + rp) * v + ov
        has_credit = is_eject | (state["fifo_size"][
            jnp.clip(recv_idx, 0, nin - 1)] < b)
        vc_free = state["out_held"][t.n_of, opc, ov] == -1
        needs_alloc = g_head & ~locked & ~is_eject
        cycf = cycle.astype(jnp.float32)
        chan_live = (jnp.floor((cycf + 1.0) * t.chan_bw)
                     - jnp.floor(cycf * t.chan_bw)) >= 1.0
        chan_live = jnp.concatenate([chan_live, jnp.zeros((1,), bool)])
        chan_ok = is_eject | chan_live[t.chan_of[t.n_of, opc]]
        elig = valid & has_credit & chan_ok & (vc_free | ~needs_alloc)

        # 5. switch allocation, round robin per output port
        in_local = nin_ar % pv
        elig2 = elig.reshape(n, pv)
        op2 = op.reshape(n, pv)
        mask_po = elig2[:, :, None] & (op2[:, :, None]
                                       == jnp.arange(p)[None, None, :])
        score = (jnp.arange(pv)[None, :, None]
                 - state["rr"][:, None, :]) % pv
        score = jnp.where(mask_po, score, _BIG)
        win = jnp.argmin(score, 1).astype(jnp.int32)
        ok = score.min(1) < _BIG
        grants = jnp.where(ok, win, -1)
        state["rr"] = jnp.where(ok, (win + 1) % pv, state["rr"])

        # 6. move the granted flits
        granted = grants >= 0
        popped = elig & (grants[t.n_of, opc] == in_local)
        win_nin = jnp.where(granted, n_ar[:, None] * pv + grants, nin)
        win_flat = jnp.clip(win_nin, 0, nin - 1).reshape(-1)
        g_ext = jnp.concatenate(
            [g_all, op[:, None], ov[:, None],
             route_phase.astype(jnp.int32)[:, None]], -1)
        w_ext = g_ext[win_flat].reshape(n, p, NF + 3)
        w_all = w_ext[..., :NF]
        w_op, w_ov, w_phase = w_ext[..., NF], w_ext[..., NF + 1], \
            w_ext[..., NF + 2]
        w_head = w_all[..., F_HEAD] != 0
        w_tail = w_all[..., F_TAIL] != 0
        state["fifo_start"] = jnp.where(popped, (st_ + 1) % b, st_)
        state["fifo_size"] = state["fifo_size"] - popped
        net = granted & (w_op != p_local)
        wopc = jnp.clip(w_op, 0, p - 1)
        dest_nei = t.neighbor[n_ar[:, None], wopc]
        dest_rp = t.recv_port[n_ar[:, None], wopc]
        dest_idx = (dest_nei * p + dest_rp) * v + w_ov
        push_rec = w_all.at[..., F_HOPS].add(1)
        push_rec = push_rec.at[..., F_PHASE].set(w_phase.astype(jnp.int32))
        state = fifo_push(state, dest_idx.reshape(-1), net.reshape(-1),
                          push_rec.reshape(-1, NF))
        set_lock = popped & g_head & ~g_tail
        clr_lock = popped & g_tail
        state["lock_op"] = jnp.where(
            set_lock, op, jnp.where(clr_lock, -1, state["lock_op"]))
        state["lock_ov"] = jnp.where(
            set_lock, ov, jnp.where(clr_lock, -1, state["lock_ov"]))
        hold_set = granted & w_head & ~w_tail & net
        hold_clr = granted & w_tail & net
        vmask = ((hold_set | hold_clr)[..., None]
                 & (jnp.arange(v)[None, None, :] == w_ov[..., None]))
        hold_val = jnp.where(hold_set, grants, -1)
        state["out_held"] = jnp.where(vmask, hold_val[..., None],
                                      state["out_held"])

        # 7. statistics
        state["node_fwd"] = state["node_fwd"] + jnp.where(
            measuring, granted.sum(1), 0)
        state["chan_fwd"] = state["chan_fwd"] + (
            net & measuring)[t.chan_src_n, t.chan_src_p]
        state["chan_seen"] = state["chan_seen"] + (
            net[t.chan_src_n, t.chan_src_p])
        ej_n = granted[:, p_local]
        wl = w_ext[:, p_local, :]
        state["eject_total"] += ej_n.sum()
        state["eject_flits"] = state["eject_flits"] + jnp.where(
            measuring, ej_n, 0)
        tail_ej = ej_n & (wl[:, F_TAIL] != 0)
        lat = (cycle - wl[:, F_TIME]) + wl[:, F_HOPS] + 1
        lat_ok = tail_ej & (wl[:, F_TIME] >= warmup)
        state["lat_sum"] += jnp.where(lat_ok, lat, 0).sum()
        state["lat_cnt"] += lat_ok.sum()
        state["lat_max"] = jnp.maximum(
            state["lat_max"], jnp.where(lat_ok, lat, 0).max())
        hbin = jnp.minimum(lat // lat_w, lat_bins - 1)
        state["lat_hist"] = state["lat_hist"].at[
            jnp.where(lat_ok, hbin, lat_bins)].add(1, mode="drop")
        # reorder tracking
        te = tail_ej
        src_safe = jnp.where(te, wl[:, F_SRC], 0)
        exp = state["exp_seq"][n_ar, src_safe]
        bits = state["rbits"][n_ar, src_safe]
        off = wl[:, F_SEQ] - exp
        in_win = (off >= 0) & (off < 32)
        off_c = jnp.clip(off, 0, 31).astype(jnp.uint32)
        bits2 = jnp.where(te & in_win, bits | (jnp.uint32(1) << off_c),
                          bits)
        run = jax.lax.population_count(bits2 & ~(bits2 + 1))
        advance = te & ((bits2 & 1) == 1)
        exp2 = jnp.where(advance, exp + run, exp)
        run_c = jnp.minimum(run, 31).astype(jnp.uint32)
        bits3 = jnp.where(advance,
                          jnp.where(run >= 32, jnp.uint32(0), bits2 >> run_c),
                          bits2)
        src_oh = te[:, None] & (n_ar[None, :] == src_safe[:, None])
        state["exp_seq"] = jnp.where(src_oh, exp2[:, None], state["exp_seq"])
        state["rbits"] = jnp.where(src_oh, bits3[:, None], state["rbits"])
        occ = jax.lax.population_count(state["rbits"]).sum(1) * l
        state["reorder_max"] = jnp.maximum(
            state["reorder_max"],
            jnp.where(measuring, occ.max(), 0).astype(jnp.int32))
        return state, None

    return step


@functools.lru_cache(maxsize=None)
def _runner(meta_key, sim_key, num_cycles):
    step = make_step(dict(meta_key), dict(sim_key))

    def run(tables, state):
        state, _ = jax.lax.scan(lambda s, c: step(tables, s, c), state,
                                jnp.arange(num_cycles))
        state["cycle0"] = state["cycle0"] + num_cycles
        return state

    return jax.jit(jax.vmap(run, in_axes=(None, 0)))


def advance(tables, states, meta, sim, num_cycles):
    """Run every lane ``num_cycles`` cycles, under the simulator's
    non-partitionable threefry bit layout."""
    fn = _runner(tuple(sorted(meta.items())), tuple(sorted(sim.items())),
                 int(num_cycles))
    with jax.threefry_partitionable(False):
        return fn(tables, states)


def occupancy(q_size, p_gen, src_queue_pkts) -> np.ndarray:
    """Per-lane source-queue occupancy over the nodes that generate."""
    io = np.asarray(p_gen) > 0
    cap = float(io.sum() * src_queue_pkts)
    q = np.asarray(q_size)
    return np.zeros(q.shape[0]) if cap <= 0 else q[:, io].sum(1) / cap


def hist_percentile(hist, bin_width, q):
    hist = np.asarray(hist, np.float64)
    total = hist.sum()
    if total <= 0:
        return 0.0
    target = q * total
    cum = np.cumsum(hist)
    b = int(np.searchsorted(cum, target))
    before = cum[b - 1] if b > 0 else 0.0
    return float((b + (target - before) / max(hist[b], 1.0)) * bin_width)


def statistics(o: dict, sim: dict, bw, *, saturated: bool) -> dict:
    """One lane's statistics, named as the simulator's result fields."""
    meas = max(int(o["meas_cnt"]), 1)
    ports = float(len(o["node_fwd"]))
    load = o["node_fwd"].astype(np.float64) / meas
    active = load[load > 1e-9]
    bw = np.asarray(bw, np.float64)
    link = o["chan_fwd"].astype(np.float64) / meas / np.where(bw > 0, bw,
                                                              1.0)
    hist, w = o["lat_hist"], sim["lat_bin_width"]
    return dict(
        throughput=int(o["eject_flits"].sum()) / meas / ports,
        offered=float(o["offered"]) / meas / ports,
        avg_latency=float(o["lat_sum"]) / max(int(o["lat_cnt"]), 1),
        max_latency=float(o["lat_max"]), node_load=load,
        lcv=float(active.std() / active.mean()) if active.size else 0.0,
        reorder_value=int(o["reorder_max"]),
        ejected_flits=int(o["eject_total"]),
        injected_flits=int(o["injected"]),
        in_flight_flits=int(o["fifo_size"].sum()),
        meas_cycles=meas, saturated=bool(saturated),
        p50_latency=hist_percentile(hist, w, 0.50),
        p90_latency=hist_percentile(hist, w, 0.90),
        p99_latency=hist_percentile(hist, w, 0.99),
        link_load_max=float(link.max()) if link.size else 0.0)


def run_campaign_lanes(grid: Grid, traffic, choice, sim: dict, points,
                       chunk: int, sat_occupancy: float = 0.9,
                       gen_dtype=np.float32) -> list[dict]:
    """A static campaign cell: every (rate, seed) lane, in ``chunk``-cycle
    slices with the campaign's saturation early exit."""
    tables, meta = build_tables(grid, traffic, choice,
                                num_vcs=sim["num_vcs"], gen_dtype=gen_dtype)
    states = make_states(meta, sim, points)
    total = sim["cycles"]
    chunk = chunk or total
    sat = np.zeros(len(points), bool)
    done = 0
    while done < total:
        k = min(chunk, total - done)
        states = advance(tables, states, meta, sim, k)
        done += k
        if done > sim["warmup"]:
            occ = occupancy(jax.device_get(states["q_size"]), tables.p_gen,
                            sim["src_queue_pkts"])
            sat |= occ >= sat_occupancy
            if done < total and sat.all():
                break
    host = jax.device_get(states)
    return [statistics(jax.tree.map(lambda x: x[i], host), sim,
                       np.ones(meta["C"]), saturated=bool(sat[i]))
            for i in range(len(points))]
