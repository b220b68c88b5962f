"""Static route-analysis tests: ``predicted_node_load`` / ``link_load``.

These two functions score a quasi-static routing table against a traffic
matrix without running the simulator — they drive the ICI link-load work
and the Fig. 1 overlays, so their accounting must be exact: conservation
properties over random traffic plus a hand-computed 3×3 fixture.
"""

import numpy as np

from _propcheck import given, settings, st
from repro.core import mesh2d, traffic, build_plan
from repro.core.bidor import bidor
from repro.core.qstar import link_load, predicted_node_load


def _xy_table(topo):
    """All-zero w_NR ⇒ every pair picks order 0 (pure XY)."""
    return bidor(topo, np.zeros(topo.num_nodes))


def _random_traffic(topo, rnd):
    n = topo.num_nodes
    t = np.array([[rnd.random() for _ in range(n)] for _ in range(n)])
    np.fill_diagonal(t, 0.0)
    if not t.sum():  # an all-zero draw is no traffic matrix: uniform
        t = 1.0 - np.eye(n)
    return t / t.sum()


# --------------------------------------------------------------------- #
# conservation properties
# --------------------------------------------------------------------- #
@settings(max_examples=15, deadline=None)
@given(st.integers(3, 6), st.integers(3, 6),
       st.randoms(use_true_random=False))
def test_link_load_conserves_total_hop_count(w, h, rnd):
    """Σ_c load_c · bw_c == Σ_{s,d} T[s,d] · dist(s,d): DOR routes are
    minimal, so every unit of traffic crosses exactly dist channels."""
    topo = mesh2d(w, h)
    t = _random_traffic(topo, rnd)
    plan = build_plan(topo, t)
    ll = link_load(topo, t, plan.table)
    expected = (t * topo.distances).sum()
    assert np.isclose((ll * topo.channel_bw).sum(), expected, rtol=1e-9)


@settings(max_examples=15, deadline=None)
@given(st.integers(3, 6), st.integers(3, 6),
       st.randoms(use_true_random=False))
def test_node_load_conserves_total_node_visits(w, h, rnd):
    """Σ_n load_n == Σ_{s,d} T[s,d] · (dist(s,d) + 1): a minimal route
    visits dist+1 nodes, endpoints included."""
    topo = mesh2d(w, h)
    t = _random_traffic(topo, rnd)
    plan = build_plan(topo, t)
    load = predicted_node_load(topo, t, plan.table)
    expected = (t * (topo.distances + 1)).sum()
    assert np.isclose(load.sum(), expected, rtol=1e-9)


@settings(max_examples=12, deadline=None)
@given(st.integers(3, 5), st.integers(3, 5), st.integers(0, 2**31 - 1))
def test_bidor_max_load_dominates_dor_on_hotspot(w, h, seed):
    """On hotspot traffic the N-Rank-guided table must not concentrate
    more load on its hottest node than plain XY does — the paper's whole
    point (§3.3: spread pairs across the XY/YX routes)."""
    topo = mesh2d(w, h)
    t = traffic.hotspot(topo, hot_frac=0.5, num_hot=1, seed=seed)
    plan = build_plan(topo, t)
    peak_xy = predicted_node_load(topo, t, _xy_table(topo)).max()
    peak_bd = predicted_node_load(topo, t, plan.table).max()
    assert peak_bd <= peak_xy + 1e-12


@settings(max_examples=12, deadline=None)
@given(st.integers(3, 5), st.integers(3, 5), st.integers(0, 2**31 - 1))
def test_bidor_max_link_load_dominates_dor_on_hotspot(w, h, seed):
    topo = mesh2d(w, h)
    t = traffic.hotspot(topo, hot_frac=0.5, num_hot=1, seed=seed)
    plan = build_plan(topo, t)
    peak_xy = link_load(topo, t, _xy_table(topo)).max()
    peak_bd = link_load(topo, t, plan.table).max()
    assert peak_bd <= peak_xy + 1e-12


# --------------------------------------------------------------------- #
# exact hand-computed 3×3 fixture
# --------------------------------------------------------------------- #
# Node ids on the 3×3 mesh (id = y*3 + x):   6 7 8
#                                            3 4 5
#                                            0 1 2
def test_single_flow_xy_route_3x3():
    """T[0,8]=1 under XY: 0→1→2→5→8 (x first, then y)."""
    topo = mesh2d(3, 3)
    t = np.zeros((9, 9))
    t[0, 8] = 1.0
    tab = _xy_table(topo)
    load = predicted_node_load(topo, t, tab)
    expected = np.zeros(9)
    expected[[0, 1, 2, 5, 8]] = 1.0
    np.testing.assert_allclose(load, expected)
    ll = link_load(topo, t, tab)
    hot = {(int(u), int(v)) for (u, v), l in zip(topo.channels, ll)
           if l > 0}
    assert hot == {(0, 1), (1, 2), (2, 5), (5, 8)}
    assert np.isclose(ll.sum(), 4.0)  # 4 channel crossings


def test_single_flow_yx_route_3x3():
    """Forcing order 1 for ⟨0, 8⟩ must walk 0→3→6→7→8 (y first)."""
    topo = mesh2d(3, 3)
    t = np.zeros((9, 9))
    t[0, 8] = 1.0
    tab = _xy_table(topo)
    choice = tab.choice.copy()
    choice[0, 8] = 1
    import dataclasses
    tab_yx = dataclasses.replace(tab, choice=choice)
    load = predicted_node_load(topo, t, tab_yx)
    expected = np.zeros(9)
    expected[[0, 3, 6, 7, 8]] = 1.0
    np.testing.assert_allclose(load, expected)
    hot = {(int(u), int(v))
           for (u, v), l in zip(topo.channels, link_load(topo, t, tab_yx))
           if l > 0}
    assert hot == {(0, 3), (3, 6), (6, 7), (7, 8)}


def test_two_weighted_flows_3x3():
    """Loads add linearly: 0→8 (w=0.75, XY) + 2→0 (w=0.25, same row)."""
    topo = mesh2d(3, 3)
    t = np.zeros((9, 9))
    t[0, 8] = 0.75
    t[2, 0] = 0.25
    tab = _xy_table(topo)
    load = predicted_node_load(topo, t, tab)
    expected = np.zeros(9)
    expected[[0, 1, 2, 5, 8]] += 0.75   # 0→1→2→5→8
    expected[[2, 1, 0]] += 0.25         # 2→1→0
    np.testing.assert_allclose(load, expected)
    ll = link_load(topo, t, tab)
    lut = {(int(u), int(v)): float(l)
           for (u, v), l in zip(topo.channels, ll)}
    assert np.isclose(lut[(0, 1)], 0.75)
    assert np.isclose(lut[(1, 0)], 0.25)  # opposite directions distinct
    assert np.isclose(lut[(2, 1)], 0.25)
    assert np.isclose(lut[(5, 8)], 0.75)
