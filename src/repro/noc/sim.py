"""Vectorized flit-level NoC simulator (replaces BookSim2 for §4).

Model (paper §4.1): input-queued wormhole routers, ``num_vcs`` virtual
channels per input port with per-VC FIFOs, credit-based flow control
(zero-delay credits — the synchronous global update reads receiver occupancy
directly), one flit per channel per cycle, round-robin switch allocation,
single-cycle routing.  The paper's 2-cycle base hop latency is realized as
1 movement/cycle plus 1 extra cycle per hop charged in latency accounting —
identical for every algorithm, so all relative comparisons are preserved.

The whole per-cycle pipeline is pure jnp and runs under ``lax.scan``; one
jit-compilation per (topology, algorithm, packet-length) triple.  By
default (``SimConfig.use_kernel``) the per-cycle transition is the fused
flit-step kernel of :mod:`repro.kernels.simstep` — one on-chip pass over
the packed flit records (the fused dense jnp body, compiled by XLA),
bit-identical to the unfused chain in :func:`_make_step`, which stays as
the differential-testing oracle.  Campaign lane batches can additionally
run under an explicit ``shard_map`` over all local devices with donated
carry buffers (:func:`get_runner` ``multi_device``).

**Routing is plan-table-driven.**  The simulator never recomputes a
dimension-order decision: every per-cycle routing step is a gather over a
:class:`repro.core.bidor.BiDORTable` artifact — ``port_tables[order, cur,
target]`` with the packet's order stamped at injection (for BiDOR, from the
plan's ``choice[s, d]``; for the DOR baselines, a constant or random order
over :func:`repro.core.bidor.dor_table`'s trivial artifact).  Tables are
traced runner arguments, so the same compiled pipeline serves ANY topology
the planning stack can produce tables for — 2D/3D meshes and tori,
concentrated and express meshes, irregular fault-region graphs
(:mod:`repro.core.topology`'s zoo) — and plan hot-swaps are plain array
replacements (:func:`retarget_tables`).

Routing algorithms (``Algo``): XY, YX, O1Turn, Valiant, ROMM (oblivious,
two-phase XY with per-phase VCs), Odd-Even (minimal adaptive, turn model of
Chiu [1]; inherently 2D), and BiDOR (this paper: quasi-static XY/YX choice
from N-Rank, VC0 = XY / VC1 = YX as in §3.3.2).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bidor import BiDORTable, dor_table
from repro.core.routes import dimension_orders, next_port_table
from repro.core.topology import Topology
from repro.obs.probe import Telemetry, resolved_epoch, telemetry_state
from .watchdog import watchdog_state
# Packed record layouts live in simconfig so the fused kernel package
# (repro.kernels.simstep) can share them without importing this module.
from .simconfig import (Algo, SimConfig, SimResult, NF, F_SRC, F_DST,
                        F_INTER, F_SEQ, F_TIME, F_HOPS, F_ORDER, F_HEAD,
                        F_TAIL, F_PHASE, NQ, Q_DST, Q_INTER, Q_ORDER,
                        Q_TIME, Q_SEQ)

_BIG = jnp.int32(1 << 30)


class _Tables(NamedTuple):
    """Static (trace-time constant) lookup tables."""

    port: jnp.ndarray      # (O, N, N) int32: plan out-port (order, cur, target)
    choice: jnp.ndarray    # (N, N) int32: plan order per (s, d)
    neighbor: jnp.ndarray  # (N, P) int32
    recv_port: jnp.ndarray  # (N, P) int32: input port at the neighbor
    cdf: jnp.ndarray       # (N, N) float32 destination CDF per source
    p_gen: jnp.ndarray     # (N,) float32 packet-generation probability @rate 1
    coords: jnp.ndarray    # (N, ndim) int32
    strides: jnp.ndarray   # (ndim,) int32: coord → node-id strides
    n_of: jnp.ndarray      # (NIN,) node of each input
    p_of: jnp.ndarray      # (NIN,) port of each input
    v_of: jnp.ndarray      # (NIN,) vc of each input
    chan_src_n: jnp.ndarray  # (C,) source node of each channel
    chan_src_p: jnp.ndarray  # (C,) output port of each channel at its source
    chan_of: jnp.ndarray   # (N, P) int32: channel at (node, out-port); C if none
    chan_bw: jnp.ndarray   # (C,) float32 relative bandwidth (0 = link down)
    esc_port: jnp.ndarray  # (N, N) int32: DOR escape table (watchdog recovery)


def _gen_tables(topo: Topology, traffic) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Packet-generation tables from a traffic matrix: per-source
    destination CDF and per-node generation probability at rate 1
    (× rate / packet_len at runtime).  Single source of truth for
    ``build_tables`` and the ``retarget_tables`` hot-swap path."""
    t = np.asarray(traffic, np.float64)
    row = t.sum(1)
    with np.errstate(invalid="ignore"):
        cdf = np.cumsum(
            np.where(row[:, None] > 0,
                     t / np.maximum(row, 1e-300)[:, None], 0), 1)
    # node share ∝ its traffic row sum; total I/O ports normalize
    p_gen = row * topo.io_weights.sum()
    return jnp.asarray(cdf, jnp.float32), jnp.asarray(p_gen, jnp.float32)


def build_tables(topo: Topology, traffic: np.ndarray,
                 table: BiDORTable | None,
                 num_vcs: int) -> tuple[_Tables, dict]:
    """Device tables for one simulation cell.

    ``table`` is the routing artifact the simulator consumes — a
    :class:`BiDORTable` with per-(order, node, destination) next-port
    tables plus the per-⟨s, d⟩ order choice.  Pass the plan's table for
    BiDOR; ``None`` routes over the trivial DOR artifact
    (:func:`repro.core.bidor.dor_table`), which the oblivious baselines
    index by constant/random order.
    """
    if table is None:
        table = dor_table(topo)
    n, p, v = topo.num_nodes, topo.num_ports, num_vcs
    port = np.asarray(table.port_tables, np.int32)
    if port.shape[1:] != (n, n):
        raise ValueError(f"port tables {port.shape} do not match {n} nodes")
    choice = np.asarray(table.choice, np.int32)
    neighbor = topo.neighbor_table.astype(np.int32)
    recv_port = np.full((n, p), 0, np.int32)
    for c in range(topo.num_channels):
        u = int(topo.channels[c, 0])
        recv_port[u, topo.channel_port[c]] = topo.port_of_channel_at_receiver[c]
    cdf, p_gen = _gen_tables(topo, traffic)
    nin = n * p * v
    idx = np.arange(nin)
    chan_of = np.full((n, p), topo.num_channels, np.int32)
    chan_of[topo.channels[:, 0], topo.channel_port] = np.arange(
        topo.num_channels, dtype=np.int32)
    tables = _Tables(
        port=jnp.asarray(port), choice=jnp.asarray(choice),
        neighbor=jnp.asarray(neighbor), recv_port=jnp.asarray(recv_port),
        cdf=cdf, p_gen=p_gen,
        coords=jnp.asarray(topo.coords.astype(np.int32)),
        strides=jnp.asarray(topo.coord_strides.astype(np.int32)),
        n_of=jnp.asarray(idx // (p * v)),
        p_of=jnp.asarray((idx // v) % p),
        v_of=jnp.asarray(idx % v),
        chan_src_n=jnp.asarray(topo.channels[:, 0].astype(np.int32)),
        chan_src_p=jnp.asarray(topo.channel_port.astype(np.int32)),
        chan_of=jnp.asarray(chan_of),
        chan_bw=jnp.asarray(topo.channel_bw, jnp.float32),
        # watchdog escape table: plain first-dimension-order DOR, built
        # from the topology alone (never from the possibly-broken plan
        # table) so it exists — and is acyclic — whatever was deployed
        esc_port=jnp.asarray(next_port_table(
            topo, dimension_orders(topo.ndim)[0]).astype(np.int32)),
    )
    meta = dict(N=n, P=p, V=v, NIN=nin, P_LOCAL=topo.port_local,
                NDIM=topo.ndim, O=port.shape[0], C=topo.num_channels)
    return tables, meta


def abstract_tables(meta: dict) -> _Tables:
    """The :class:`_Tables` a cell traces, as shapes only — one
    :class:`jax.ShapeDtypeStruct` per field, derived from ``meta``
    without building a topology or plan.

    Single source of truth for the kernel package's capacity math
    (``repro.kernels.simstep.ops.state_footprint_bytes`` and the blocked
    tile chooser): the VMEM gate sizes the *actual* traced operands
    instead of a hand-maintained byte formula.  A drift test
    (``tests/test_simstep_kernel.py``) pins every field's shape and
    dtype against real :func:`build_tables` output across the topology
    zoo, so this mirror cannot silently disagree with reality."""
    n, p, nin, c = meta["N"], meta["P"], meta["NIN"], meta["C"]
    nd, o = meta["NDIM"], meta["O"]

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    return _Tables(
        port=s((o, n, n)), choice=s((n, n)), neighbor=s((n, p)),
        recv_port=s((n, p)), cdf=s((n, n), jnp.float32),
        p_gen=s((n,), jnp.float32), coords=s((n, nd)), strides=s((nd,)),
        n_of=s((nin,)), p_of=s((nin,)), v_of=s((nin,)),
        chan_src_n=s((c,)), chan_src_p=s((c,)), chan_of=s((n, p)),
        chan_bw=s((c,), jnp.float32), esc_port=s((n, n)))


def source_queue_meta(tables: _Tables,
                      cfg: SimConfig) -> tuple[np.ndarray, float]:
    """(io_mask, qcap) for :func:`queue_occupancy` — one ``p_gen`` device
    read.  Compute once per cell (or after a traffic retarget) and pass
    through; deriving it inside every chunk of an early-exit loop costs a
    host transfer per chunk for a value that only changes when the
    generation tables do."""
    io_mask = np.asarray(jax.device_get(tables.p_gen)) > 0
    qcap = float(io_mask.sum() * cfg.src_queue_pkts)
    return io_mask, qcap


def queue_occupancy(tables: _Tables, cfg: SimConfig,
                    q_size, meta: tuple[np.ndarray, float] | None = None,
                    ) -> np.ndarray:
    """Per-lane source-queue occupancy fraction over the I/O-capable
    nodes — the lane-saturation criterion shared by the campaign
    early-exit and the control plane's saturation flag.  ``meta`` is the
    precomputed :func:`source_queue_meta`; omitting it re-derives the
    mask from the device tables on every call.

    A pattern with no I/O-capable sources (all-zero generation rows,
    e.g. a fully-shed fault-region matrix) has ``qcap == 0``; its lanes
    can never queue a packet, so their occupancy is 0.0 by definition —
    NOT NaN, which would poison the ``>=`` saturation comparison and the
    early-exit downstream."""
    io_mask, qcap = source_queue_meta(tables, cfg) if meta is None else meta
    q = np.asarray(jax.device_get(q_size))
    if qcap <= 0:
        return np.zeros(q.shape[0])
    return q[:, io_mask].sum(1) / qcap


def retarget_tables(tables: _Tables, topo: Topology, *,
                    traffic: np.ndarray | None = None,
                    choice: np.ndarray | None = None,
                    channel_bw: np.ndarray | None = None) -> _Tables:
    """Plan hot-swap path: a new `_Tables` with only the requested fields
    replaced.

    Tables are *traced* runner arguments, so swapping them between chunks
    re-uses the cached jit compilation and leaves all in-flight state
    (buffers, locks, source queues, statistics) untouched — the mechanism
    behind the quasi-static control plane (:mod:`repro.noc.ctrl`):

    * ``traffic`` — new generation matrix (destination CDF + per-node
      injection probability are rebuilt; drift epochs).
    * ``choice`` — new BiDOR plan; only packets generated after the swap
      follow it, in-flight packets keep the order stamped at injection.
    * ``channel_bw`` — link fail/recover/degrade events.

    Passing nothing returns an identical table set (the empty-schedule
    identity asserted by ``tests/test_ctrl.py``).
    """
    kw = {}
    if traffic is not None:
        kw["cdf"], kw["p_gen"] = _gen_tables(topo, traffic)
    if choice is not None:
        kw["choice"] = jnp.asarray(np.asarray(choice, np.int32))
    if channel_bw is not None:
        kw["chan_bw"] = jnp.asarray(np.asarray(channel_bw), jnp.float32)
    return tables._replace(**kw) if kw else tables


def fresh_state(meta: dict, cfg: SimConfig):
    """Per-run dynamic state — a flat dict of arrays, hence a pytree that
    can be stacked/vmapped over a leading batch axis (one lane per
    (rate, seed) campaign point)."""
    n, nin = meta["N"], meta["NIN"]
    b, q = cfg.buf_per_vc, cfg.src_queue_pkts
    i32 = jnp.int32
    z = functools.partial(jnp.zeros, dtype=i32)
    # optional time-resolved probes (repro.obs.probe) and stall watchdog
    # (repro.noc.watchdog); {} when off, so a probe-free state pytree is
    # unchanged key for key
    tel = telemetry_state(meta, cfg)
    wd = watchdog_state(meta, cfg)
    return dict(
        **tel,
        **wd,
        # per-input-VC FIFOs: packed flit records (see NF layout above)
        flits=z((nin, b, NF)),
        fifo_start=z((nin,)), fifo_size=z((nin,)),
        # wormhole locks
        lock_op=jnp.full((nin,), -1, i32), lock_ov=jnp.full((nin,), -1, i32),
        out_held=jnp.full((n, meta["P"], meta["V"]), -1, i32),
        rr=z((n, meta["P"])),
        # source queues: packed packet records (see NQ layout above)
        qpkts=z((n, q, NQ)),
        q_start=z((n,)), q_size=z((n,)), prog=z((n,)),
        next_seq=z((n, n)),
        # destination-side reorder tracking (paper §4.1 'Reorder Value')
        exp_seq=z((n, n)), rbits=jnp.zeros((n, n), jnp.uint32),
        # statistics
        node_fwd=z((n,)), eject_flits=z((n,)), chan_fwd=z((meta["C"],)),
        chan_seen=z((meta["C"],)),
        lat_sum=z(()), lat_cnt=z(()), lat_max=z(()),
        lat_hist=z((cfg.lat_bins,)),
        reorder_max=z(()), injected=z(()), offered=z(()), dropped=z(()),
        eject_total=z(()), meas_cnt=z(()),
        rate=jnp.float32(0.0),
        cycle0=jnp.int32(0),   # absolute-cycle offset (chunks / segments)
        # phase boundaries (dynamic, per run): injection and measurement
        # stop at these absolute cycles; the tail is the drain phase.
        inject_until=jnp.int32(cfg.cycles - cfg.drain),
        measure_until=jnp.int32(cfg.cycles - cfg.drain),
        key=jax.random.PRNGKey(cfg.seed),
    )


def _popcount(x):
    return jax.lax.population_count(x)


def _make_step(meta: dict, cfg: SimConfig):
    """Build the per-cycle transition function (tables traced, so all
    traffic patterns and injection rates share one compilation per algo).

    With ``cfg.use_kernel`` (the default) the transition is the fused
    flit-step kernel (:mod:`repro.kernels.simstep`: the fused dense jnp
    body, compiled by XLA on every backend) — bit-identical to the
    unfused chain below, which remains the differential-testing oracle
    and the ``simstep_scale`` benchmark baseline."""
    if cfg.use_kernel:
        from repro.kernels import simstep  # deferred: avoids an import
        return simstep.make_step(meta, cfg)  # cycle with repro.noc
    algo = Algo(cfg.algo)
    n, p, v, nin = meta["N"], meta["P"], meta["V"], meta["NIN"]
    p_local = meta["P_LOCAL"]
    num_orders = meta["O"]
    if algo == Algo.ODDEVEN and meta["NDIM"] != 2:
        raise ValueError("odd-even routing is a 2D turn model; "
                         f"topology has ndim={meta['NDIM']}")
    b, q, l = cfg.buf_per_vc, cfg.src_queue_pkts, cfg.packet_len
    pv = p * v
    n_arange = jnp.arange(n)
    nin_arange = jnp.arange(nin)
    two_phase = algo in (Algo.VALIANT, Algo.ROMM)
    tel_epoch = resolved_epoch(cfg)  # 0 ⇔ telemetry off
    watchdog = bool(cfg.watchdog)

    def fifo_push(state, idx, ok, records):
        """Append packed flit ``records`` (K, NF) to FIFOs ``idx`` where
        ``ok`` — ONE scatter with a contiguous NF-word payload."""
        slot = (state["fifo_start"][idx] + state["fifo_size"][idx]) % b
        safe_idx = jnp.where(ok, idx, nin)  # out of range ⇒ dropped
        state["flits"] = state["flits"].at[safe_idx, slot].set(
            records, mode="drop")
        state["fifo_size"] = state["fifo_size"].at[safe_idx].add(
            1, mode="drop")
        return state

    def gen_metadata(t, key, src, dst):
        """Per-algo packet metadata: (order, inter)."""
        k1, k2, k3 = jax.random.split(key, 3)
        if algo == Algo.XY:
            order = jnp.zeros(n, jnp.int32)
        elif algo == Algo.YX:
            # last order is the descending one ("YX" on 2D, and its k-dim
            # generalization when a k-orders plan table is in play)
            order = jnp.full((n,), num_orders - 1, jnp.int32)
        elif algo == Algo.O1TURN:
            order = jnp.where(jax.random.bernoulli(k1, 0.5, (n,)),
                              num_orders - 1, 0).astype(jnp.int32)
        elif algo == Algo.BIDOR:
            order = t.choice[src, dst]
        else:
            order = jnp.zeros(n, jnp.int32)
        if algo == Algo.VALIANT:
            inter = jax.random.randint(k2, (n,), 0, n)
        elif algo == Algo.ROMM:
            cs, cd = t.coords[src], t.coords[dst]
            lo = jnp.minimum(cs, cd)
            hi = jnp.maximum(cs, cd)
            u = jax.random.uniform(k3, (n, lo.shape[-1]))
            ic = lo + (u * (hi - lo + 1)).astype(jnp.int32)
            ic = jnp.clip(ic, lo, hi)
            inter = (ic * t.strides).sum(-1)
        else:
            inter = jnp.full((n,), -1, jnp.int32)
        return order, inter

    def oddeven_route(t, cur, src, target, free_by_port):
        """Chiu's minimal adaptive odd-even ROUTE + credit-based selection.

        Ports: 0=+x(E) 1=−x(W) 2=+y 3=−y.  Returns the chosen port.
        """
        cx = t.coords[cur, 0]
        sx = t.coords[src, 0]
        dx = t.coords[target, 0] - cx
        dy = t.coords[target, 1] - t.coords[cur, 1]
        y_port = jnp.where(dy > 0, 2, 3)
        east_ok = (dx > 0) & ((dy == 0)
                              | (t.coords[target, 0] % 2 == 1) | (dx != 1))
        y_ok_east = (dx > 0) & (dy != 0) & ((cx % 2 == 1) | (cx == sx))
        west_ok = dx < 0
        y_ok_west = (dx < 0) & (dy != 0) & (cx % 2 == 0)
        y_ok_straight = (dx == 0) & (dy != 0)
        x_port = jnp.where(dx > 0, 0, 1)
        x_ok = east_ok | west_ok
        y_ok = y_ok_east | y_ok_west | y_ok_straight
        fx = jnp.take_along_axis(free_by_port, x_port[:, None], 1)[:, 0]
        fy = jnp.take_along_axis(free_by_port, y_port[:, None], 1)[:, 0]
        prefer_y = y_ok & ((~x_ok) | (fy > fx))
        return jnp.where(prefer_y, y_port, x_port), x_ok, y_ok

    def step(t, state, cycle):
        cycle = state["cycle0"] + cycle    # absolute cycle across segments
        key, kg, kd, km, kv = jax.random.split(state["key"], 5)
        state["key"] = key
        # warmup → measure → drain phasing: statistics only inside the
        # measurement window, no new packets once the drain phase starts.
        measuring = (cycle >= cfg.warmup) & (cycle < state["measure_until"])
        state["meas_cnt"] += measuring.astype(jnp.int32)

        # ---------------- 1. packet generation (open loop) -------------- #
        u = jax.random.uniform(kg, (n,))
        gen = (u < (t.p_gen * (state["rate"] / l))) \
            & (cycle < state["inject_until"])
        if watchdog:
            # livelock throttle: mask generation at throttled sources —
            # mask only, the RNG stream above is drawn unconditionally,
            # so throttling never perturbs other sources' randomness
            gen = gen & (state["wd_throttle"] <= 0)
            state["wd_throttle"] = jnp.maximum(state["wd_throttle"] - 1, 0)
        ud = jax.random.uniform(kd, (n,))
        dst = jnp.clip((t.cdf <= ud[:, None]).sum(1), 0, n - 1).astype(jnp.int32)
        order, inter = gen_metadata(t, km, n_arange, dst)
        space = state["q_size"] < q
        push = gen & space
        seq = state["next_seq"][n_arange, dst]
        # dense one-hot update: row s bumps column dst[s] (rows distinct)
        state["next_seq"] = state["next_seq"] + (
            push[:, None] & (n_arange[None, :] == dst[:, None]))
        slot = (state["q_start"] + state["q_size"]) % q
        row = jnp.where(push, n_arange, n)  # drop when not pushing
        qrec = jnp.stack(
            [dst, inter, order, jnp.full((n,), cycle, jnp.int32), seq], -1)
        state["qpkts"] = state["qpkts"].at[row, slot].set(qrec, mode="drop")
        state["q_size"] = state["q_size"] + push
        state["offered"] += jnp.where(measuring, gen.sum(), 0)
        state["dropped"] += jnp.where(measuring, (gen & ~space).sum(), 0)

        # ---------------- 2. flit injection (1/cycle/node) -------------- #
        hs = state["q_start"]
        hpkt = state["qpkts"][n_arange, hs]  # (N, NQ)
        h_dst = hpkt[:, Q_DST]
        h_inter = hpkt[:, Q_INTER]
        h_order = hpkt[:, Q_ORDER]
        h_seq = hpkt[:, Q_SEQ]
        h_time = hpkt[:, Q_TIME]
        fl_head = state["prog"] == 0
        fl_tail = state["prog"] == l - 1
        phase0 = (h_inter < 0) | (h_inter == n_arange)
        if algo in (Algo.XY, Algo.YX):
            vc_in = (n_arange + h_dst) % v
        elif algo in (Algo.O1TURN, Algo.BIDOR):
            vc_in = h_order % v
        elif two_phase:
            vc_in = phase0.astype(jnp.int32) % v
        else:  # ODDEVEN: local VC with more space
            base = (n_arange * p + p_local) * v
            sizes = jnp.stack([state["fifo_size"][base + k]
                               for k in range(v)], 1)
            vc_in = jnp.argmin(sizes, 1).astype(jnp.int32)
        lf_idx = (n_arange * p + p_local) * v + vc_in
        can = (state["q_size"] > 0) & (state["fifo_size"][lf_idx] < b)
        inj_rec = jnp.stack(
            [n_arange, h_dst, h_inter, h_seq, h_time,
             jnp.zeros(n, jnp.int32), h_order, fl_head.astype(jnp.int32),
             fl_tail.astype(jnp.int32), phase0.astype(jnp.int32)], -1)
        state = fifo_push(state, lf_idx, can, inj_rec)
        state["prog"] = jnp.where(can, state["prog"] + 1, state["prog"])
        done = can & (state["prog"] >= l)
        state["prog"] = jnp.where(done, 0, state["prog"])
        state["q_start"] = jnp.where(done, (hs + 1) % q, hs)
        state["q_size"] = state["q_size"] - done
        state["injected"] += can.sum()

        # ---------------- 3. head-of-line + routing --------------------- #
        st_ = state["fifo_start"]
        g_all = state["flits"][nin_arange, st_]  # (NIN, NF) one gather
        g = dict(src=g_all[:, F_SRC], dst=g_all[:, F_DST],
                 inter=g_all[:, F_INTER], seq=g_all[:, F_SEQ],
                 time=g_all[:, F_TIME], hops=g_all[:, F_HOPS],
                 order=g_all[:, F_ORDER], head=g_all[:, F_HEAD] != 0,
                 tail=g_all[:, F_TAIL] != 0, phase=g_all[:, F_PHASE] != 0)
        valid = state["fifo_size"] > 0
        route_phase = g["phase"] | (g["inter"] < 0) | (g["inter"] == t.n_of)
        target = jnp.where(route_phase, g["dst"], g["inter"])
        target = jnp.clip(target, 0, n - 1)
        at_dest = target == t.n_of
        locked = state["lock_op"] >= 0

        # receiver free space per (input, port): for adaptive selection
        if algo == Algo.ODDEVEN:
            recv_base = (t.neighbor * p + t.recv_port) * v  # (N, P)
            free_pv = jnp.stack(
                [b - state["fifo_size"][recv_base + k] for k in range(v)],
                -1)  # (N, P, V)
            free_port_total = free_pv.sum(-1)  # (N, P)
            op_ad, _, _ = oddeven_route(
                t, t.n_of, g["src"], target, free_port_total[t.n_of])
            # VC choice: freer VC at the chosen port, must be un-held
            held = state["out_held"][t.n_of, op_ad] >= 0  # (NIN, V)
            f = free_pv[t.n_of, op_ad]  # (NIN, V)
            f = jnp.where(held, -1, f)
            ov_route = jnp.argmax(f, -1).astype(jnp.int32)
            op_route = op_ad
        else:
            if algo == Algo.XY:
                eff_order = jnp.zeros(nin, jnp.int32)
            elif algo == Algo.YX:
                eff_order = jnp.full((nin,), num_orders - 1, jnp.int32)
            elif two_phase:
                eff_order = jnp.zeros(nin, jnp.int32)
            else:
                eff_order = g["order"]
            op_route = t.port[eff_order, t.n_of, target]
            if algo in (Algo.XY, Algo.YX):
                ov_route = t.v_of
            elif two_phase:
                ov_route = route_phase.astype(jnp.int32) % v
            else:
                ov_route = g["order"] % v
        op = jnp.where(at_dest, p_local, op_route)
        ov = jnp.where(at_dest, 0, ov_route)
        op = jnp.where(locked, state["lock_op"], op)
        ov = jnp.where(locked, state["lock_ov"], ov)
        if watchdog:
            # deadlock escape: a head stalled past the threshold misroutes
            # one hop via the acyclic DOR escape table ON THE HIGHEST VC
            # (Duato-style escape lane — the wedged cycle holds the lower
            # classes, so the escape hop has somewhere to drain to), then
            # routes normally (body flits follow the head's locked
            # port/VC; the escape still goes through eligibility + credit
            # + allocation — a misroute, never a teleport)
            esc = (state["wd_stall"] >= cfg.wd_stall_cycles) \
                & valid & g["head"] & ~locked & ~at_dest
            op = jnp.where(esc, t.esc_port[t.n_of, target], op)
            ov = jnp.where(esc, v - 1, ov)

        # ---------------- 4. eligibility -------------------------------- #
        is_eject = op == p_local
        nei = t.neighbor[t.n_of, jnp.clip(op, 0, p - 1)]
        rp = t.recv_port[t.n_of, jnp.clip(op, 0, p - 1)]
        recv_idx = (nei * p + rp) * v + ov
        has_credit = is_eject | (state["fifo_size"][
            jnp.clip(recv_idx, 0, nin - 1)] < b)
        vc_free = state["out_held"][t.n_of, jnp.clip(op, 0, p - 1), ov] == -1
        needs_alloc = g["head"] & ~locked & ~is_eject
        # fractional channel bandwidth: channel c may transmit this cycle
        # iff the fixed-rate service schedule ⌊(cyc+1)·bw⌋ − ⌊cyc·bw⌋ fires
        # (bw = 1 ⇒ every cycle, bit-identical to the ungated simulator;
        # bw = 0 ⇒ never — a dead link).  Degraded links come from the
        # control plane's fault events (repro.noc.ctrl).
        cycf = cycle.astype(jnp.float32)
        chan_live = (jnp.floor((cycf + 1.0) * t.chan_bw)
                     - jnp.floor(cycf * t.chan_bw)) >= 1.0
        chan_live = jnp.concatenate(
            [chan_live, jnp.zeros((1,), bool)])  # sentinel: no channel
        chan_ok = is_eject | chan_live[
            t.chan_of[t.n_of, jnp.clip(op, 0, p - 1)]]
        elig = valid & has_credit & chan_ok & (vc_free | ~needs_alloc)

        # ---------------- 5. switch allocation (round-robin) ------------ #
        # all output ports allocated at once: score (N, PV, P), winner per
        # (node, port) column — ports are independent, so this is exactly
        # the per-port round-robin pick
        in_local = nin_arange % pv  # input index within its node
        elig2 = elig.reshape(n, pv)
        op2 = op.reshape(n, pv)
        mask_po = elig2[:, :, None] & (op2[:, :, None]
                                       == jnp.arange(p)[None, None, :])
        score = (jnp.arange(pv)[None, :, None]
                 - state["rr"][:, None, :]) % pv
        score = jnp.where(mask_po, score, _BIG)
        win = jnp.argmin(score, 1).astype(jnp.int32)      # (N, P)
        ok = score.min(1) < _BIG
        grants = jnp.where(ok, win, -1)
        state["rr"] = jnp.where(ok, (win + 1) % pv, state["rr"])

        # ---------------- 6. move granted flits ------------------------- #
        granted = grants >= 0  # (N, P)
        # input-centric pop flag: input i moved iff it won its output port
        popped = elig & (grants[t.n_of, jnp.clip(op, 0, p - 1)] == in_local)
        win_nin = jnp.where(granted,
                            n_arange[:, None] * pv + grants, nin)  # drop idx
        win_flat = jnp.clip(win_nin, 0, nin - 1).reshape(-1)
        # winner records + routing decision, ONE gather of NF+3 words
        g_ext = jnp.concatenate(
            [g_all, op[:, None], ov[:, None],
             route_phase.astype(jnp.int32)[:, None]], -1)
        w_ext = g_ext[win_flat].reshape(n, p, NF + 3)
        w_all = w_ext[..., :NF]
        w_op = w_ext[..., NF]
        w_ov = w_ext[..., NF + 1]
        w_phase = w_ext[..., NF + 2]
        w = dict(head=w_all[..., F_HEAD] != 0, tail=w_all[..., F_TAIL] != 0)
        # pops (elementwise — ``popped`` marks at most one flit per input)
        state["fifo_start"] = jnp.where(popped, (st_ + 1) % b, st_)
        state["fifo_size"] = state["fifo_size"] - popped
        # pushes (network ports only): one packed scatter
        net = granted & (w_op != p_local)
        dest_nei = t.neighbor[n_arange[:, None], jnp.clip(w_op, 0, p - 1)]
        dest_rp = t.recv_port[n_arange[:, None], jnp.clip(w_op, 0, p - 1)]
        dest_idx = (dest_nei * p + dest_rp) * v + w_ov
        push_rec = w_all.at[..., F_HOPS].add(1)
        push_rec = push_rec.at[..., F_PHASE].set(w_phase.astype(jnp.int32))
        state = fifo_push(state, dest_idx.reshape(-1), net.reshape(-1),
                          push_rec.reshape(-1, NF))
        # wormhole locks (elementwise): set on head (non-tail), clear on tail
        set_lock_i = popped & g["head"] & ~g["tail"]
        clr_lock_i = popped & g["tail"]
        state["lock_op"] = jnp.where(
            set_lock_i, op, jnp.where(clr_lock_i, -1, state["lock_op"]))
        state["lock_ov"] = jnp.where(
            set_lock_i, ov, jnp.where(clr_lock_i, -1, state["lock_ov"]))
        # out_held bookkeeping (elementwise over (N, P, V); net ports only)
        hold_set = granted & w["head"] & ~w["tail"] & net
        hold_clr = granted & w["tail"] & net
        vmask = ((hold_set | hold_clr)[..., None]
                 & (jnp.arange(v)[None, None, :] == w_ov[..., None]))
        hold_val = jnp.where(hold_set, grants, -1)
        state["out_held"] = jnp.where(vmask, hold_val[..., None],
                                      state["out_held"])
        if watchdog:
            # stall age: +1 per cycle an occupied input fails to move,
            # reset on movement; deadlock trip counted exactly at the
            # threshold crossing (once per stall episode)
            new_stall = jnp.where(valid & ~popped, state["wd_stall"] + 1, 0)
            state["wd_trips"] = state["wd_trips"].at[0].add(
                (new_stall == cfg.wd_stall_cycles).sum())
            state["wd_stall"] = new_stall
            # livelock: a moved flit whose hop count passes the limit
            # throttles its source (set, not add: re-trips re-arm it);
            # trip counted once per flit at the exact crossing
            hops_now = push_rec[..., F_HOPS]
            lv = net & (hops_now > cfg.wd_hop_limit)
            lv_src = jnp.where(lv, w_all[..., F_SRC], n)
            state["wd_throttle"] = state["wd_throttle"].at[
                lv_src.reshape(-1)].set(cfg.wd_throttle_cycles, mode="drop")
            state["wd_trips"] = state["wd_trips"].at[1].add(
                (net & (hops_now == cfg.wd_hop_limit + 1)).sum())

        # ---------------- 7. statistics --------------------------------- #
        state["node_fwd"] = state["node_fwd"] + jnp.where(
            measuring, granted.sum(1), 0)
        # per-channel forwarded flits (link loads / max-link-load roofline):
        # channel c moved a flit iff its source (node, port) granted a
        # network move — a gather at compile-time-constant indices
        state["chan_fwd"] = state["chan_fwd"] + (
            net & measuring)[t.chan_src_n, t.chan_src_p]
        # always-on per-channel counter (control plane's drift detector
        # needs link profiles during warmup and drain too)
        state["chan_seen"] = state["chan_seen"] + (
            net[t.chan_src_n, t.chan_src_p])
        # ejects only ever leave through the local output port, so all
        # eject/latency/reorder statistics live on its (N,) column
        ej_n = granted[:, p_local]
        wl = w_ext[:, p_local, :]  # (N, NF+3) local-port winner records
        state["eject_total"] += ej_n.sum()
        state["eject_flits"] = state["eject_flits"] + jnp.where(
            measuring, ej_n, 0)
        # latency at tail ejects, for packets generated in the measurement
        # window (drain-phase landings of measured packets still count)
        tail_ej = ej_n & (wl[:, F_TAIL] != 0)
        lat = (cycle - wl[:, F_TIME]) + wl[:, F_HOPS] + 1  # +1: eject hop
        lat_ok = tail_ej & (wl[:, F_TIME] >= cfg.warmup)
        state["lat_sum"] += jnp.where(lat_ok, lat, 0).sum()
        state["lat_cnt"] += lat_ok.sum()
        state["lat_max"] = jnp.maximum(
            state["lat_max"], jnp.where(lat_ok, lat, 0).max())
        # latency histogram (percentiles); last bin is the overflow bucket
        hbin = jnp.minimum(lat // cfg.lat_bin_width, cfg.lat_bins - 1)
        state["lat_hist"] = state["lat_hist"].at[
            jnp.where(lat_ok, hbin, cfg.lat_bins)].add(1, mode="drop")
        # reorder tracking (≤ 1 tail eject per node per cycle: the local port)
        te = tail_ej
        src_v = wl[:, F_SRC]
        seq_v = wl[:, F_SEQ]
        src_safe = jnp.where(te, src_v, 0)
        exp = state["exp_seq"][n_arange, src_safe]
        bits = state["rbits"][n_arange, src_safe]
        off = seq_v - exp
        in_win = (off >= 0) & (off < 32)
        off_c = jnp.clip(off, 0, 31).astype(jnp.uint32)
        bits2 = jnp.where(te & in_win,
                          bits | (jnp.uint32(1) << off_c),
                          bits)
        lowmask = (bits2 & ~(bits2 + 1))  # trailing ones
        run = _popcount(lowmask)
        advance = te & ((bits2 & 1) == 1)
        exp2 = jnp.where(advance, exp + run, exp)
        run_c = jnp.minimum(run, 31).astype(jnp.uint32)
        bits3 = jnp.where(advance,
                          jnp.where(run >= 32, jnp.uint32(0), bits2 >> run_c),
                          bits2)
        src_oh = te[:, None] & (n_arange[None, :] == src_safe[:, None])
        state["exp_seq"] = jnp.where(src_oh, exp2[:, None],
                                     state["exp_seq"])
        state["rbits"] = jnp.where(src_oh, bits3[:, None], state["rbits"])
        occ = _popcount(state["rbits"]).sum(1) * l
        state["reorder_max"] = jnp.maximum(
            state["reorder_max"],
            jnp.where(measuring, occ.max(), 0).astype(jnp.int32))

        # ------------- 8. telemetry probes (optional) ------------------- #
        # Time-resolved ring buffers (repro.obs.probe): reads existing
        # cycle values, writes only tel_* arrays, consumes no RNG — so
        # every core statistic is bit-identical with telemetry on or off,
        # and absent entirely when off.  Slot index wraps (accumulating);
        # tel_cycles normalizes.  Mirrored op for op in the fused body
        # (repro.kernels.simstep.ref).
        if tel_epoch:
            slot = (cycle // tel_epoch) % cfg.tel_slots
            state["tel_cycles"] = state["tel_cycles"].at[slot].add(1)
            state["tel_chan"] = state["tel_chan"].at[slot].add(
                net[t.chan_src_n, t.chan_src_p].astype(jnp.int32))
            state["tel_counts"] = state["tel_counts"].at[slot].add(
                jnp.stack([gen.sum(), push.sum(), (gen & ~space).sum(),
                           tail_ej.sum()]).astype(jnp.int32))
            nb = cfg.tel_occ_bins
            obin = jnp.minimum(state["q_size"].sum() * nb // (n * q),
                               nb - 1)
            state["tel_qocc"] = state["tel_qocc"].at[slot, obin].add(1)
            state["tel_lat"] = state["tel_lat"].at[
                slot, jnp.where(tail_ej, hbin, cfg.lat_bins)].add(
                1, mode="drop")
        return state, None

    return step


class _PinnedPrng:
    """A jitted runner, traced and run under the non-partitionable
    threefry bit layout.

    Every simulated statistic is a pure function of its point's threefry
    stream, and the goldens pin the streams of that layout.  JAX 0.5 made
    the partitionable layout the default, which draws other bits from the
    same key.  The layout is part of the jit key, so the pin holds for the
    runner's own calls only and leaves the process-wide default alone."""

    def __init__(self, fn):
        self._fn = fn

    def __call__(self, *args):
        with jax.threefry_partitionable(False):
            return self._fn(*args)

    def lower(self, *args):
        with jax.threefry_partitionable(False):
            return self._fn.lower(*args)


@functools.lru_cache(maxsize=None)
def _get_runner(meta_key: tuple, cfg_key: tuple, num_cycles: int):
    """One jit compilation per (mesh size, algo, flow-control params,
    cycle-chunk length); vmapped over batched per-run states — the batch
    axis carries (injection-rate, seed) campaign points — and shared
    across traffic patterns (tables are traced arguments)."""
    meta = dict(meta_key)
    cfg = SimConfig(**dict(cfg_key))
    step = _make_step(meta, cfg)

    def run(tables, state):
        state, _ = jax.lax.scan(
            lambda s, c: step(tables, s, c), state, jnp.arange(num_cycles))
        state["cycle0"] = state["cycle0"] + num_cycles
        return state

    return _PinnedPrng(jax.jit(jax.vmap(run, in_axes=(None, 0))))


@functools.lru_cache(maxsize=None)
def _get_sharded_runner(meta_key: tuple, cfg_key: tuple, num_cycles: int,
                        ndev: int):
    """shard_map lane-parallel variant of :func:`_get_runner`.

    Lanes are fully independent, so splitting the batch axis over an
    explicit ("lane",) device mesh is exact — every lane runs the same
    per-cycle ops on the same bits, each device just owns its slice.
    The carry state is donated: chunked campaigns and the control
    plane's epoch loop update multi-MB flit buffers in place instead of
    reallocating them per call.
    """
    from jax.sharding import Mesh, PartitionSpec

    meta = dict(meta_key)
    cfg = SimConfig(**dict(cfg_key))
    step = _make_step(meta, cfg)

    def run(tables, state):
        state, _ = jax.lax.scan(
            lambda s, c: step(tables, s, c), state, jnp.arange(num_cycles))
        state["cycle0"] = state["cycle0"] + num_cycles
        return state

    mesh = Mesh(np.array(jax.devices()[:ndev]), ("lane",))
    fn = jax.shard_map(jax.vmap(run, in_axes=(None, 0)), mesh=mesh,
                       in_specs=(PartitionSpec(), PartitionSpec("lane")),
                       out_specs=PartitionSpec("lane"), check_vma=False)
    return _PinnedPrng(jax.jit(fn, donate_argnums=(1,)))


def _cfg_key(cfg: SimConfig) -> tuple:
    """Compile-relevant SimConfig fields (rate and seed are dynamic)."""
    return tuple(sorted(dict(
        algo=int(cfg.algo), num_vcs=cfg.num_vcs, buf_per_vc=cfg.buf_per_vc,
        packet_len=cfg.packet_len, src_queue_pkts=cfg.src_queue_pkts,
        cycles=cfg.cycles, warmup=cfg.warmup, drain=cfg.drain,
        lat_bins=cfg.lat_bins, lat_bin_width=cfg.lat_bin_width,
        use_kernel=bool(cfg.use_kernel),
        sim_tile_nodes=int(cfg.sim_tile_nodes),
        telemetry=bool(cfg.telemetry),
        tel_epoch=cfg.tel_epoch, tel_slots=cfg.tel_slots,
        tel_occ_bins=cfg.tel_occ_bins, watchdog=bool(cfg.watchdog),
        wd_stall_cycles=cfg.wd_stall_cycles,
        wd_hop_limit=cfg.wd_hop_limit,
        wd_throttle_cycles=cfg.wd_throttle_cycles).items()))


def get_runner(meta: dict, cfg: SimConfig, num_cycles: int, *,
               num_lanes: int | None = None,
               multi_device: bool | None = None):
    """Public cached-runner accessor (used by :mod:`repro.noc.campaign`
    and :mod:`repro.noc.ctrl`).

    ``multi_device`` selects the ``shard_map`` lane-parallel runner:
    ``True`` forces it (raises if the ``num_lanes`` batch does not
    divide over the local devices), ``False`` pins the single-device
    runner, and ``None`` — the default — auto-enables it whenever more
    than one local device is visible and ``num_lanes`` divides evenly.
    Both runners produce bit-identical states (asserted by
    ``tests/test_multidevice.py``)."""
    ndev = jax.device_count()
    want = (multi_device if multi_device is not None
            else ndev > 1 and num_lanes is not None
            and num_lanes % ndev == 0)
    if want:
        if ndev <= 1:
            raise ValueError("multi_device=True with a single device; "
                             "on CPU expose cores via XLA_FLAGS="
                             "--xla_force_host_platform_device_count=N")
        if num_lanes is None or num_lanes % ndev:
            raise ValueError(
                f"multi_device=True needs the lane count to divide over "
                f"the devices ({num_lanes} lanes, {ndev} devices)")
        return _get_sharded_runner(tuple(sorted(meta.items())),
                                   _cfg_key(cfg), int(num_cycles), ndev)
    return _get_runner(tuple(sorted(meta.items())), _cfg_key(cfg),
                       int(num_cycles))


def runner_builds() -> int:
    """Runners :func:`get_runner` has built in this process (its caches'
    misses).  A call that raises the count built a runner, which
    compiles on its first call."""
    return (_get_runner.cache_info().misses
            + _get_sharded_runner.cache_info().misses)


def hist_percentile(hist: np.ndarray, bin_width: int, q: float) -> float:
    """q-quantile (0 < q < 1) from a fixed-width latency histogram, with
    linear interpolation inside the bin.  The last bin is an overflow
    bucket, so quantiles landing there are lower bounds."""
    hist = np.asarray(hist, dtype=np.float64)
    total = hist.sum()
    if total <= 0:
        return 0.0
    target = q * total
    cum = np.cumsum(hist)
    b = int(np.searchsorted(cum, target))
    before = cum[b - 1] if b > 0 else 0.0
    frac = (target - before) / max(hist[b], 1.0)
    return float((b + frac) * bin_width)


def postprocess(o: dict, cfg: SimConfig, topo: Topology, *,
                rate: float, seed: int, saturated: bool = False,
                meas_cycles: int | None = None) -> SimResult:
    """Turn one run's device state (already on host) into a SimResult."""
    meas = int(o["meas_cnt"]) if meas_cycles is None else int(meas_cycles)
    meas = max(meas, 1)
    ports = float(topo.io_weights.sum())
    load = o["node_fwd"].astype(np.float64) / meas
    active = load[load > 1e-9]
    lat_cnt = max(int(o["lat_cnt"]), 1)
    bw = np.asarray(topo.channel_bw, np.float64)
    flits = o["chan_fwd"].astype(np.float64) / meas
    # dead (bw = 0) channels never forward, so 0/0 → 0 by convention
    link = flits / np.where(bw > 0, bw, 1.0)
    hist = o["lat_hist"]
    return SimResult(
        algo=Algo(cfg.algo), injection_rate=float(rate),
        throughput=int(o["eject_flits"].sum()) / meas / ports,
        offered=float(o["offered"]) / meas / ports,
        avg_latency=float(o["lat_sum"]) / lat_cnt,
        max_latency=float(o["lat_max"]),
        node_load=load,
        lcv=float(active.std() / active.mean()) if active.size else 0.0,
        reorder_value=int(o["reorder_max"]),
        ejected_flits=int(o["eject_total"]),
        injected_flits=int(o["injected"]),
        in_flight_flits=int(o["fifo_size"].sum()),
        seed=int(seed),
        meas_cycles=meas,
        saturated=bool(saturated),
        p50_latency=hist_percentile(hist, cfg.lat_bin_width, 0.50),
        p90_latency=hist_percentile(hist, cfg.lat_bin_width, 0.90),
        p99_latency=hist_percentile(hist, cfg.lat_bin_width, 0.99),
        link_load_max=float(link.max()) if link.size else 0.0,
    )


def point_key(seed: int, rate: float) -> jnp.ndarray:
    """PRNG stream of a (rate, seed) campaign point: a pure function of
    the point itself (the float32 bit pattern of the rate is folded in),
    so a point gets the identical stream whether it runs alone, inside a
    sweep, or as any lane of a batched campaign."""
    rate_bits = int(np.float32(rate).view(np.uint32))
    return jax.random.fold_in(jax.random.PRNGKey(seed), rate_bits)


def make_states(meta: dict, cfg: SimConfig,
                points: list[tuple[float, int]]):
    """Batched fresh state for a list of (rate, seed) points."""
    states = []
    for rate, seed in points:
        st = fresh_state(meta, cfg)
        st["rate"] = jnp.float32(rate)
        st["key"] = point_key(seed, rate)
        states.append(st)
    return maybe_shard_states(jax.tree.map(lambda *xs: jnp.stack(xs),
                                           *states))


def maybe_shard_states(batched):
    """Shard the lane (batch) axis across local devices when possible.

    Lanes are fully independent, so SPMD partitioning of the leading axis
    is exact: results are bit-identical to the unsharded run, each device
    just executes its slice of lanes in parallel.  No-op on a single
    device or when the batch does not divide evenly.  On CPU, expose
    cores as devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    the first jax import), as ``benchmarks/run.py`` does.
    """
    ndev = jax.device_count()
    nb = jax.tree.leaves(batched)[0].shape[0]
    if ndev <= 1 or nb % ndev:
        return batched
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.array(jax.devices()), ("lane",))
    spec = NamedSharding(mesh, PartitionSpec("lane"))
    return jax.tree.map(lambda x: jax.device_put(x, spec), batched)


def static_bw_slots(topo: Topology, cfg: SimConfig) -> np.ndarray:
    """(tel_slots, C) per-slot bandwidth for a run with no fault events:
    every slot sees the topology's static channel bandwidths."""
    return np.broadcast_to(
        np.asarray(topo.channel_bw, np.float64),
        (int(cfg.tel_slots), topo.num_channels)).copy()


def run_sweep(topo: Topology, traffic: np.ndarray, cfg: SimConfig,
              rates: list[float],
              bidor_table: BiDORTable | None = None,
              seeds: list[int] | None = None, *,
              return_telemetry: bool = False,
              return_watchdog: bool = False):
    """Run a batch of simulations over (rate, seed) points in ONE jitted,
    vmapped call.  Results are ordered rate-major: ``[(r, s) for r in
    rates for s in seeds]``; with ``seeds=None`` (default ``[cfg.seed]``)
    this is the legacy one-result-per-rate list.

    ``return_telemetry=True`` returns ``(results, telemetry)`` instead —
    the lane-major :class:`repro.obs.probe.Telemetry` bundle (None when
    ``cfg.telemetry`` is off).  ``return_watchdog=True`` appends the
    all-lane :class:`repro.noc.watchdog.WatchdogReport` (None when
    ``cfg.watchdog`` is off) as the trailing element."""
    table = None
    if cfg.algo == Algo.BIDOR:
        if bidor_table is None:
            raise ValueError("BIDOR needs a BiDORTable")
        table = bidor_table
    tables, meta = build_tables(topo, traffic, table, cfg.num_vcs)
    runner = get_runner(meta, cfg, cfg.cycles)
    points = [(r, s) for r in rates for s in (seeds or [cfg.seed])]
    batched = make_states(meta, cfg, points)
    out = jax.device_get(runner(tables, batched))
    results = [postprocess(jax.tree.map(lambda x: x[i], out), cfg, topo,
                           rate=r, seed=s)
               for i, (r, s) in enumerate(points)]
    extras: list = []
    if return_telemetry:
        tel = Telemetry.from_state(out, cfg)
        if tel is not None:
            tel = tel.with_bw(static_bw_slots(topo, cfg))
        extras.append(tel)
    if return_watchdog:
        from .watchdog import WatchdogReport
        extras.append(WatchdogReport.from_state(out, cfg))
    if not extras:
        return results
    return (results, *extras)


def run_sim(topo: Topology, traffic: np.ndarray, cfg: SimConfig,
            bidor_table: BiDORTable | None = None, *,
            return_telemetry: bool = False,
            return_watchdog: bool = False):
    """Run one simulation and post-process statistics.  With
    ``return_telemetry=True``, returns ``(SimResult, Telemetry | None)``;
    with ``return_watchdog=True``, the
    :class:`repro.noc.watchdog.WatchdogReport` (or None) is appended."""
    out = run_sweep(topo, traffic, cfg, [cfg.injection_rate],
                    bidor_table, return_telemetry=return_telemetry,
                    return_watchdog=return_watchdog)
    if return_telemetry or return_watchdog:
        results, *extras = out
        return (results[0], *extras)
    return out[0]


def run_trace_sweep(topo: Topology,
                    segments: list[tuple[np.ndarray, float]],
                    cfg: SimConfig,
                    bidor_table: BiDORTable | None = None,
                    seeds: list[int] | None = None):
    """Trace-driven simulation: piecewise-constant traffic epochs, batched
    (vmapped) over seeds.

    Each segment is (traffic_matrix, injection_rate); the network state
    (buffers, in-flight packets, reorder bookkeeping) carries across
    segments.  Used for the paper's realistic-workload evaluation (§4.3),
    where a leaf-switch port-pair trace is replayed as epochs.  BiDOR's
    routing table stays fixed (built offline from the aggregate statistics),
    while adaptive routing reacts per cycle — exactly the paper's contrast.

    Returns a list over seeds of (SimResult over all measured cycles,
    per-segment LCVs).
    """
    table = None
    if cfg.algo == Algo.BIDOR:
        if bidor_table is None:
            raise ValueError("BIDOR needs a BiDORTable")
        table = bidor_table
    seeds = list(seeds or [cfg.seed])
    nb = len(seeds)
    batched = None
    lcvs: list[list[float]] = [[] for _ in seeds]
    prev_fwd = None
    for si, (tm, rate) in enumerate(segments):
        tables, meta = build_tables(topo, tm, table, cfg.num_vcs)
        runner = get_runner(meta, cfg, cfg.cycles)
        if batched is None:
            states = []
            for seed in seeds:
                st = fresh_state(meta, cfg)
                st["key"] = jax.random.fold_in(
                    jax.random.PRNGKey(seed), si)
                # traces run open-ended: every segment injects and
                # measures for its full cfg.cycles window
                st["inject_until"] = _BIG
                st["measure_until"] = _BIG
                states.append(st)
            batched = maybe_shard_states(
                jax.tree.map(lambda *xs: jnp.stack(xs), *states))
            prev_fwd = np.zeros((nb, meta["N"]), np.int64)
        batched["rate"] = jnp.full((nb,), rate, jnp.float32)
        batched = runner(tables, batched)
        fwd = np.asarray(jax.device_get(batched["node_fwd"]), np.int64)
        seg = fwd - prev_fwd
        prev_fwd = fwd
        for bi in range(nb):
            active = seg[bi][seg[bi] > 0]
            if active.size:
                lcvs[bi].append(float(active.std() / active.mean()))
    out = jax.device_get(batched)
    mean_rate = float(np.mean([r for _, r in segments]))
    return [(postprocess(jax.tree.map(lambda x: x[bi], out), cfg, topo,
                         rate=mean_rate, seed=seeds[bi]), lcvs[bi])
            for bi in range(nb)]


def run_trace(topo: Topology, segments: list[tuple[np.ndarray, float]],
              cfg: SimConfig,
              bidor_table: BiDORTable | None = None):
    """Single-seed :func:`run_trace_sweep` — returns (SimResult, lcvs)."""
    return run_trace_sweep(topo, segments, cfg, bidor_table)[0]
