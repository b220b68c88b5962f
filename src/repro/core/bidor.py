"""BiDOR — bi-modal dimension-order routing guided by N-Rank (paper §3.3).

For every ⟨s, d⟩, compare the cumulative ``w_NR`` along the XY and YX routes
(eq. 10) and pick the cheaper one; the choice is stored one bit per
destination in a per-source bitmap (eq. 11) for O(1) runtime lookup.

``bidor_k`` generalizes the binary choice to all k! dimension orders on
k-dimensional topologies (used for the multi-pod ICI fabric); with
``orders=dimension_orders(2)`` it reduces exactly to the paper's scheme.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
from operator import itemgetter

import numpy as np

from .topology import Topology
from .routes import (dimension_orders, next_port_table, route_costs,
                     route_links)

__all__ = ["BiDORTable", "bidor", "bidor_k", "dor_table", "TIE_TOL"]

# Relative tolerance of the eq. 10 minimization's tie detection.  Shared
# with the device-resident pipeline (repro.core.plan_fast), whose choice
# tables must be identical to this oracle's.
TIE_TOL = 1e-5


@dataclasses.dataclass(frozen=True)
class BiDORTable:
    """Offline routing artifact deployed to the routers.

    Attributes:
      choice: (N, N) int8 — DOR-order index for every ⟨s, d⟩ (0 = XY).
        For the binary paper scheme this *is* the bitmap of eq. (11):
        ``bitmap[s] = choice[s, :]``.
      orders: the dimension orders the indices refer to.
      costs: (len(orders), N, N) cumulative w_NR per route (diagnostics).
      port_tables: (len(orders), N, N) int8 — next output port for
        (current-node, destination) under each order; routers follow
        ``port_tables[choice[s, d], cur, d]``.
    """

    choice: np.ndarray
    orders: tuple[tuple[int, ...], ...]
    costs: np.ndarray
    port_tables: np.ndarray
    # (N, N) bool — pairs for which NO dimension order avoids the down
    # channels (set by fault-aware planning; None on intact topologies).
    # Their traffic must be shed (admission control) — the stored choice
    # would cross a dead link.
    unroutable: np.ndarray | None = None

    @property
    def bitmaps(self) -> np.ndarray:
        """Per-source |N|-bitmaps (eq. 11); valid for the binary scheme."""
        if len(self.orders) > 2:
            raise ValueError("bitmaps are defined for the binary (XY/YX) scheme")
        return self.choice.astype(np.uint8)

    def packed_bitmaps(self) -> np.ndarray:
        """(N, ceil(N/8)) uint8 — the hardware bitmap layout."""
        return np.packbits(self.bitmaps, axis=1)


def dor_table(topo: Topology,
              orders: list[tuple[int, ...]] | None = None) -> BiDORTable:
    """Plan-table artifact for plain dimension-order routing.

    The table-routed simulator consumes (``port_tables``, ``choice``) for
    EVERY algorithm; the DOR baselines (XY, YX, O1Turn, Valiant, ROMM)
    route over this trivial artifact — binary orders, all-XY choice, no
    costs — so the simulator needs no routing logic of its own beyond the
    table gather.
    """
    if orders is None:
        orders = dimension_orders(topo.ndim, binary_only=True)
    n = topo.num_nodes
    ports = np.stack([next_port_table(topo, o) for o in orders])
    return BiDORTable(choice=np.zeros((n, n), np.int8),
                      orders=tuple(map(tuple, orders)),
                      costs=np.zeros((len(orders), n, n)),
                      port_tables=ports)


def route_feasibility(topo: Topology,
                      orders: list[tuple[int, ...]],
                      down: np.ndarray) -> np.ndarray:
    """(O, N, N) bool — order o's DOR route s→d avoids every down channel.

    ``down`` is a boolean per-channel mask (or an index array) over
    ``topo.channels``.  Works on the *intact* channel indexing: DOR routes
    are functions of coordinates alone, so feasibility is just a walk of
    each route against the down set.
    """
    from .routes import walk_routes

    down = np.asarray(down)
    if down.dtype != bool:
        m = np.zeros(topo.num_channels, dtype=bool)
        m[down] = True
        down = m
    n = topo.num_nodes
    down_pair = np.zeros((n, n), dtype=bool)
    down_pair[topo.channels[down, 0], topo.channels[down, 1]] = True
    feas = np.ones((len(orders), n, n), dtype=bool)
    for oi, order in enumerate(orders):
        seq = walk_routes(topo, order)               # (N, N, L+1)
        for h in range(seq.shape[-1] - 1):
            a, b = seq[..., h], seq[..., h + 1]
            hit = (a != b) & down_pair[a, b]
            feas[oi] &= ~hit
    return feas


def bidor_k(topo: Topology, w_nr: np.ndarray,
            orders: list[tuple[int, ...]] | None = None,
            tie_break: str = "xy",
            down_channels: np.ndarray | None = None) -> BiDORTable:
    """Choose, per ⟨s, d⟩, the DOR order with minimal Σ w_NR (eq. 10).

    ``tie_break``: "xy" (paper default — lowest order index) or "hash"
    (deterministic per-pair split across tied orders).  Flip-symmetric
    patterns (Overturn) tie on EVERY pair; measurements (EXPERIMENTS.md
    §Fidelity) show tie→XY dominates, so it stays the default.

    ``down_channels`` (fault-aware planning): boolean mask or index array
    over ``topo.channels`` of hard-failed channels.  Orders whose route
    crosses a down channel are masked out of the eq. (10) minimization, so
    every selected route stays a pure DOR route inside its own VC class —
    the fallback keeps the quasi-static scheme deadlock-free by
    construction.  Pairs no order can serve are flagged in
    ``BiDORTable.unroutable`` (their traffic must be shed upstream).
    """
    if orders is None:
        orders = dimension_orders(topo.ndim)
    costs = route_costs(topo, w_nr, orders)          # (O, N, N)
    unroutable = None
    if down_channels is not None and np.asarray(down_channels).size:
        feas = route_feasibility(topo, orders, down_channels)
        unroutable = ~feas.any(axis=0)
        np.fill_diagonal(unroutable, False)
        # infeasible orders leave the minimization; unroutable pairs keep
        # their unmasked costs so `choice` stays well-defined (and shed).
        big = np.where(unroutable[None], costs, np.inf)
        costs = np.where(feas, costs, big)
    # Ties are resolved with a tolerance (w_NR is float32; ties on
    # symmetric topologies are symmetry-exact) and broken by a
    # deterministic per-pair hash across the tied orders.  Flip-symmetric
    # patterns (e.g. Overturn) tie on EVERY pair — always defaulting to XY
    # would degenerate BiDOR to pure XY there, contradicting the paper's
    # own Table 1; the hash splits tied pairs evenly while staying fully
    # deterministic/offline (same bitmap artifact, same in-order property).
    n = topo.num_nodes
    best = costs.min(axis=0)
    tol = TIE_TOL * (1.0 + np.abs(best))
    is_min = costs <= best + tol                      # (O, N, N)
    if tie_break == "hash":
        num_min = is_min.sum(axis=0)                  # (N, N)
        sid = np.arange(n, dtype=np.uint64)
        mix = (sid[:, None] * np.uint64(2654435761)
               ^ (sid[None, :] * np.uint64(40503) + np.uint64(0x9E3779B9)))
        rank = ((mix >> np.uint64(13)).astype(np.int64)
                % np.maximum(num_min, 1))
        cum = np.cumsum(is_min, axis=0) - 1           # rank of tied order
        pick = is_min & (cum == rank[None])
        choice = np.argmax(pick, axis=0).astype(np.int8)
    else:
        choice = np.argmax(is_min, axis=0).astype(np.int8)  # first minimal
    np.fill_diagonal(choice, 0)
    ports = np.stack([next_port_table(topo, o) for o in orders])
    return BiDORTable(choice=choice, orders=tuple(map(tuple, orders)),
                      costs=costs, port_tables=ports,
                      unroutable=unroutable)


def bidor(topo: Topology, w_nr: np.ndarray,
          down_channels: np.ndarray | None = None) -> BiDORTable:
    """Paper-faithful binary BiDOR: XY vs YX only."""
    return bidor_k(topo, w_nr, dimension_orders(topo.ndim, binary_only=True),
                   down_channels=down_channels)


# Route structure of the last fabric BiDOR-G refined, keyed on what DOR
# walks depend on (orders, coordinates, channels): successive replans of
# one fabric change traffic and bandwidth, never the walks.
_ROUTES: dict = {}


@dataclasses.dataclass(frozen=True)
class _Routes:
    """Traffic-free route data of every pair (row ``s * N + d``); the
    arrays are read-only."""

    links: list       # per order, (N*N, L) int32 channel ids; −1 = none
    hops: list        # per order, (N*N,) route lengths
    # per (order, alternative): which of the alternative's links the
    # order's route also uses, (N*N, L) bool; and whether a pair can move
    # from the one to the other, (N*N,) bool: both routes lie inside the
    # graph and their links differ
    shared: dict
    movable: dict


def _route_structure(topo: Topology, orders) -> tuple[_Routes, bool]:
    """:class:`_Routes` of ``topo``, and whether it came from the cache."""
    key = (tuple(orders), topo.dims, topo.wrap, topo.coords.tobytes(),
           topo.channels.tobytes())
    routes = _ROUTES.get(key)
    if routes is not None:
        return routes, True
    n2 = topo.num_nodes ** 2
    links, hops = [], []
    for o in orders:
        lo, ho = route_links(topo, o)
        links.append(lo.reshape(n2, -1))
        hops.append(ho.reshape(n2))
    on = [np.arange(lo.shape[1]) < ho[:, None] for lo, ho in zip(links, hops)]
    inside = [~(m & (lo < 0)).any(1) for m, lo in zip(on, links)]
    shared, movable = {}, {}
    for o in range(len(orders)):
        for a in range(len(orders)):
            if a == o:
                continue
            sh = np.zeros(links[a].shape, dtype=bool)
            for h in range(links[o].shape[1]):
                sh |= (links[a] == links[o][:, h:h + 1]) & on[o][:, h:h + 1]
            sh &= on[a]
            same = (sh.sum(1) == hops[a]) & (hops[a] == hops[o])
            shared[o, a] = sh
            movable[o, a] = inside[o] & inside[a] & ~same
    for arr in (*links, *hops, *shared.values(), *movable.values()):
        arr.flags.writeable = False
    routes = _Routes(links=links, hops=hops, shared=shared, movable=movable)
    _ROUTES.clear()
    _ROUTES[key] = routes
    return routes, False


def _rows(arr: np.ndarray, lens: np.ndarray) -> list[list]:
    """The first ``lens[i]`` entries of each row of ``arr``, as lists."""
    flat = arr[np.arange(arr.shape[1]) < lens[:, None]].tolist()
    ends = np.cumsum(lens).tolist()
    return [flat[a:b] for a, b in zip([0] + ends[:-1], ends)]


def _getter(links: list) -> itemgetter:
    """``itemgetter`` of ``links`` that returns a tuple even for one."""
    return itemgetter(*links) if len(links) > 1 else itemgetter(*links, *links)


@contextlib.contextmanager
def _cyclic_gc_paused():
    """Hold the cyclic garbage collector.  BiDOR-G builds hundreds of
    thousands of small acyclic lists and tuples, which the collector
    would otherwise rescan, with every object of the process, as they
    pile up."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def greedy_refine(topo: Topology, traffic, table: BiDORTable,
                  sweeps: int = 4, *, stats: dict | None = None
                  ) -> BiDORTable:
    """BiDOR-G (beyond paper): greedy max-link-load refinement.

    BiDOR minimizes each pair's *own* path cost against the static w_NR
    field; it never sees the load its choice induces on others.  BiDOR-G
    post-processes the table: sweep pairs in decreasing traffic order and
    flip a pair's dimension order whenever that lowers the peak link
    load among the links it would use (loads kept incrementally).  Still
    fully offline/quasi-static — the output is the same bitmap artifact.

    The sweep is sequential — each decision reads the loads the pairs
    before it left — and in float64.  What it reads per pair is prepared
    with array ops, so the loop touches Python lists only: each route's
    links, and for each alternative its links grouped by the load a move
    adds to them (``t[s, d] / bw[c]``; 0 on links it shares with the
    current route).  Within a group the peak is ``max(loads) + added``,
    which equals the largest ``load + added`` exactly (rounding is
    monotone).  A pair whose orders all walk the same links, or that has
    a single order inside the graph, can never flip and is not swept.

    ``stats``, when given, receives the work counts: ``pairs`` (pairs
    with traffic), ``visited`` (those of them each sweep visits: the
    pairs that could flip), ``sweeps_run``, ``changed`` (entries of the
    output that differ from the input table), ``route_cache_hit``
    (the route structure came from the previous call's topology),
    ``orders`` (the dimension orders it chooses between) and ``hops``
    (the route hops of every order, summed over the visited pairs: the
    links a sweep weighs).
    """
    from .qstar import link_load

    t = np.asarray(traffic, dtype=np.float64)
    n = topo.num_nodes
    orders = table.orders
    routes, hit = _route_structure(topo, orders)
    choice = table.choice.copy()
    load = link_load(topo, t, table,
                     links=[lo.reshape(n, n, -1) for lo in routes.links])

    # pairs with traffic in decreasing order (stable: row-major on ties),
    # then those that could flip
    cand = (t > 0) & ~np.eye(n, dtype=bool)
    if table.unroutable is not None:
        cand &= ~table.unroutable
    idx = np.flatnonzero(cand)
    idx = idx[np.argsort(-t.ravel()[idx], kind="stable")]
    can_flip = np.zeros(idx.size, dtype=bool)
    for m in routes.movable.values():
        can_flip |= m[idx]
    pairs, idx = idx.size, idx[can_flip]

    bw = np.where(topo.channel_bw > 0, topo.channel_bw, 1e-12)
    bwl = bw.tolist()
    tp = t.ravel()[idx]
    tl = tp.tolist()
    with _cyclic_gc_paused():
        lk = [lo[idx] for lo in routes.links]
        hp = [h[idx] for h in routes.hops]
        route = [_rows(lo, h) for lo, h in zip(lk, hp)]
        peak_of = [[_getter(r) for r in ro] for ro in route]
        on = [np.arange(lo.shape[1]) < h[:, None] for lo, h in zip(lk, hp)]
        even = [((bw[lo] == bw[lo[:, :1]]) | ~m).all(1)
                for lo, m in zip(lk, on)]
        first = [(tp / bw[lo[:, 0]]).tolist() for lo in lk]
        # moves[o][p]: the orders pair p can move to from order o, each
        # with its links grouped by the load the move adds to them
        moves = []
        for o in range(len(orders)):
            per_alt = []
            for a in range(len(orders)):
                if a == o:
                    continue
                ok = routes.movable[o, a][idx]
                sh = routes.shared[o, a][idx]
                # most moves add one load to every link: no link shared,
                # one bandwidth along the route
                plain = ok & even[a] & ~sh.any(1)
                opts = [(a, ((g, x),)) if pl else None for pl, g, x in
                        zip(plain.tolist(), peak_of[a], first[a])]
                for p in np.flatnonzero(ok & ~plain).tolist():
                    by = {}
                    for c, s in zip(route[a][p], sh[p].tolist()):
                        by.setdefault(0.0 if s else tl[p] / bwl[c],
                                      []).append(c)
                    opts[p] = (a, tuple((_getter(cs), x)
                                        for x, cs in by.items()))
                per_alt.append(opts)
            moves.append([tuple(filter(None, m)) for m in zip(*per_alt)])

        ld = load.tolist()
        ch = choice.ravel()[idx].tolist()
        sweeps_run = 0
        for _ in range(sweeps):
            sweeps_run += 1
            changed = 0
            for p, cur in enumerate(ch):
                options = moves[cur][p]
                if not options:
                    continue
                best_oi, best_peak = cur, max(peak_of[cur][p](ld))
                for oi, groups in options:
                    # peak among the links the pair would use if moved
                    peak = 0.0
                    for g, x in groups:
                        v = max(g(ld)) + x
                        if v > peak:
                            peak = v
                    if peak < best_peak - 1e-15:
                        best_oi, best_peak = oi, peak
                if best_oi != cur:
                    x = tl[p]
                    for c in route[cur][p]:
                        ld[c] -= x / bwl[c]
                    for c in route[best_oi][p]:
                        ld[c] += x / bwl[c]
                    ch[p] = best_oi
                    changed += 1
            if changed == 0:
                break
    choice.flat[idx] = ch
    if stats is not None:
        stats.update(pairs=pairs, visited=int(idx.size),
                     sweeps_run=sweeps_run,
                     changed=int((choice != table.choice).sum()),
                     route_cache_hit=hit, orders=len(orders),
                     hops=int(sum(h.sum() for h in hp)))
    return BiDORTable(choice=choice, orders=orders, costs=table.costs,
                      port_tables=table.port_tables,
                      unroutable=table.unroutable)
