"""BiDOR-G's prepared sweep against the per-hop loop it replaced: the
same choice table, bit for bit, and the same work counts, on meshes,
tori, degraded fabrics, k-order tables and traffic with ties."""

import dataclasses

import numpy as np
import pytest

from repro.core import link_load, mesh2d, multipod, torus
from repro.core.bidor import BiDORTable, bidor, bidor_k, greedy_refine
from repro.core.routes import dimension_orders, walk_routes


# ---------------------------------------------------------------------- #
# the oracle: BiDOR-G as a Python loop over pairs and hops, with the
# link load it started from
# ---------------------------------------------------------------------- #
def _oracle_link_load(topo, traffic, table):
    load = np.zeros(topo.num_channels, dtype=np.float64)
    seqs = [walk_routes(topo, o) for o in table.orders]
    t = np.asarray(traffic, dtype=np.float64)
    if table.unroutable is not None:
        t = np.where(table.unroutable, 0.0, t)
    n = topo.num_nodes
    chan_lut = np.full((n, n), -1, dtype=np.int64)
    chan_lut[topo.channels[:, 0], topo.channels[:, 1]] = np.arange(
        topo.num_channels)
    for oi, seq in enumerate(seqs):
        sel = table.choice == oi
        w = np.where(sel, t, 0.0)
        hops = seq.shape[-1]
        for h in range(hops - 1):
            a, b = seq[..., h], seq[..., h + 1]
            moving = (a != b) & (chan_lut[a, b] >= 0)
            if not (a != b).any():
                break
            ids = chan_lut[a[moving], b[moving]]
            np.add.at(load, ids, w[moving])
    bw = topo.channel_bw
    with np.errstate(divide="ignore", invalid="ignore"):
        out = np.where(bw > 0, load / np.where(bw > 0, bw, 1.0),
                       np.where(load > 0, np.inf, 0.0))
    return out


def _oracle_refine(topo, traffic, table, sweeps=4, *, stats=None):
    import numpy as _np

    t = _np.asarray(traffic, dtype=_np.float64)
    n = topo.num_nodes
    orders = table.orders
    seqs = [walk_routes(topo, o) for o in orders]
    chan_lut = _np.full((n, n), -1, _np.int64)
    chan_lut[topo.channels[:, 0], topo.channels[:, 1]] = _np.arange(
        topo.num_channels)

    def pair_links(oi, s, d):
        seq = seqs[oi][s, d]
        ids = []
        for h in range(len(seq) - 1):
            a, b = int(seq[h]), int(seq[h + 1])
            if a == b:
                break
            c = int(chan_lut[a, b])
            if c < 0:
                return None
            ids.append(c)
        return ids

    choice = table.choice.copy()
    load = _oracle_link_load(topo, t,
                             BiDORTable(choice=choice, orders=orders,
                                        costs=table.costs,
                                        port_tables=table.port_tables,
                                        unroutable=table.unroutable))
    bw = _np.where(topo.channel_bw > 0, topo.channel_bw, 1e-12)
    unroutable = table.unroutable
    pairs = [(s, d) for s in range(n) for d in range(n)
             if s != d and t[s, d] > 0
             and not (unroutable is not None and unroutable[s, d])]
    pairs.sort(key=lambda p: -t[p])
    sweeps_run = 0
    for _ in range(sweeps):
        sweeps_run += 1
        changed = 0
        for s, d in pairs:
            cur = int(choice[s, d])
            cur_links = pair_links(cur, s, d)
            if cur_links is None:
                continue
            best_oi, best_peak = cur, max(
                (load[c] for c in cur_links), default=0.0)
            for oi in range(len(orders)):
                if oi == cur:
                    continue
                alt = pair_links(oi, s, d)
                if alt is None:
                    continue
                peak = 0.0
                for c in alt:
                    peak = max(peak, load[c]
                               + (0 if c in cur_links else t[s, d] / bw[c]))
                if peak < best_peak - 1e-15:
                    best_oi, best_peak = oi, peak
            if best_oi != cur:
                for c in cur_links:
                    load[c] -= t[s, d] / bw[c]
                for c in pair_links(best_oi, s, d):
                    load[c] += t[s, d] / bw[c]
                choice[s, d] = best_oi
                changed += 1
        if changed == 0:
            break
    if stats is not None:
        stats.update(pairs=len(pairs), sweeps_run=sweeps_run,
                     changed=int((choice != table.choice).sum()))
    return choice


# ---------------------------------------------------------------------- #
# cases
# ---------------------------------------------------------------------- #
def _traffic(n, density, seed, decimals=None):
    rng = np.random.default_rng(seed)
    t = rng.random((n, n)) * (rng.random((n, n)) < density)
    if decimals is not None:
        t = np.round(t, decimals)
    np.fill_diagonal(t, 0.0)
    return t


def _degrade(topo, down):
    bw = topo.channel_bw.copy()
    bw[down] = 0.0
    return dataclasses.replace(topo, channel_bw=bw)


def _case(name):
    """(topology, traffic, table, sweeps) of a named case."""
    kind, _, sweeps = name.rpartition("-s")
    sweeps = int(sweeps)
    rng = np.random.default_rng(len(name))
    if kind in ("mesh8", "torus8"):
        topo = mesh2d(8, 8) if kind == "mesh8" else torus(8, 8)
        t = _traffic(64, 0.5, 1)
        return topo, t, bidor(topo, rng.random(64)), sweeps
    if kind == "torus16":
        topo = torus(16, 16)
        t = _traffic(256, 0.2, 2)
        return topo, t, bidor(topo, rng.random(256)), sweeps
    if kind == "torus8-degraded":
        topo = torus(8, 8)
        # every channel out of node 9, and three more
        down = np.concatenate([np.flatnonzero(topo.channels[:, 0] == 9),
                               [40, 41, 100]])
        ptopo = _degrade(topo, down)
        table = bidor(ptopo, rng.random(64), down_channels=down)
        assert table.unroutable.sum() >= 63
        return ptopo, _traffic(64, 0.6, 3), table, sweeps
    if kind == "torus8-dropped":
        # the graph itself loses channels: some routes leave it
        topo = torus(8, 8).degrade([5, 6, 70, 71], drop=True)
        return topo, _traffic(64, 0.6, 4), bidor(topo, rng.random(64)), sweeps
    if kind == "torus444-k6":
        topo = torus(4, 4, 4)
        table = bidor_k(topo, rng.random(64), dimension_orders(3))
        assert len(table.orders) == 6
        return topo, _traffic(64, 0.5, 5), table, sweeps
    if kind == "torus8-ties":
        topo = torus(8, 8)
        t = _traffic(64, 0.7, 6, decimals=3)
        assert len(np.unique(t[t > 0])) < (t > 0).sum()
        return topo, t, bidor(topo, rng.random(64)), sweeps
    if kind == "multipod-bw":
        # slower inter-pod links, and a lane at half width: the load a
        # move adds differs along a route
        topo = multipod(2, 4, 4)
        bw = topo.channel_bw.copy()
        bw[[3, 17, 30]] *= 0.5
        topo = dataclasses.replace(topo, channel_bw=bw)
        n = topo.num_nodes
        return topo, _traffic(n, 0.5, 7), bidor(topo, rng.random(n)), sweeps
    raise KeyError(name)


CASES = ([f"{k}-s{s}" for k in ("mesh8", "torus8") for s in (1, 2, 4)]
         + ["torus16-s2", "torus8-degraded-s2", "torus8-dropped-s2",
            "torus444-k6-s2", "torus8-ties-s2", "multipod-bw-s2"])


@pytest.mark.parametrize("name", CASES)
def test_refine_matches_the_per_hop_loop(name):
    topo, t, table, sweeps = _case(name)
    want_stats, got_stats = {}, {}
    before = table.choice.copy()
    want = _oracle_refine(topo, t, table, sweeps=sweeps, stats=want_stats)
    got = greedy_refine(topo, t, table, sweeps=sweeps, stats=got_stats)
    assert np.array_equal(got.choice, want)
    assert got.choice.dtype == table.choice.dtype
    for key in ("pairs", "sweeps_run", "changed"):
        assert got_stats[key] == want_stats[key], key
    assert want_stats["changed"] > 0          # the case exercises flips
    assert 0 < got_stats["visited"] <= got_stats["pairs"]
    assert np.array_equal(table.choice, before)   # the input is kept


@pytest.mark.parametrize("name", ["mesh8-s2", "torus8-degraded-s2",
                                  "torus8-dropped-s2", "torus444-k6-s2",
                                  "multipod-bw-s2"])
def test_link_load_sums_as_the_walk_did(name):
    topo, t, table, _ = _case(name)
    assert np.array_equal(link_load(topo, t, table),
                          _oracle_link_load(topo, t, table))


def test_route_structure_is_cached_per_fabric():
    topo, t, table, _ = _case("torus8-s2")
    first, again, other = {}, {}, {}
    greedy_refine(mesh2d(3, 3), np.ones((9, 9)), bidor(mesh2d(3, 3),
                                                        np.zeros(9)))
    greedy_refine(topo, t, table, sweeps=1, stats=first)
    # new bandwidths, same walks: the cache holds
    slow = dataclasses.replace(topo, channel_bw=topo.channel_bw * 0.5)
    greedy_refine(slow, t, table, sweeps=1, stats=again)
    greedy_refine(mesh2d(8, 8), t, bidor(mesh2d(8, 8), np.zeros(64)),
                  sweeps=1, stats=other)
    assert (first["route_cache_hit"], again["route_cache_hit"],
            other["route_cache_hit"]) == (False, True, False)
