"""The reference fabric (``qsbench.ref.grid``) in any number of
dimensions: unchanged in 2-D, and equal to the program's topologies,
output ports and dimension-order routes on 2-D and 3-D tori."""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

from bench_tiny import BENCH  # noqa: F401  (puts bench/ on the path)

# sha256 (first 16 hex digits) of dtype, shape and bytes of each 2-D
# output, taken from the reference as it stood when it took 2-D fabrics
# only.  Every cell's job stream, reference tables and re-simulated
# lanes are built from these.  ``walk`` on the 32x32 mesh is 528 MB; it
# is a function of ``next_hop`` and the horizon alone, which are pinned.
FROZEN_2D = {
    "mesh4": {
        "coords": "637b815c3e30f06c", "channels": "17334e57b29b0d33",
        "channel_ports": "0f5cf731c0526e2e", "neighbors": "7e2d35595ac1c7eb",
        "uniform": "8a7301fb25c3534f", "transpose": "cbb1ff644b83d7e3",
        "next_hop0": "5bccc3ef56627951", "next_port0": "7fd26f094f8d2220",
        "walk0": "49ce5d4fb4acaf68", "next_hop1": "5a5113c477e3c3f2",
        "next_port1": "8c39b6b65c27d5db", "walk1": "78fc03ee3ef99e12",
    },
    "mesh32": {
        "coords": "ae073f6f9bea1760", "channels": "adfa482aa139ae1c",
        "channel_ports": "1983871b17bb19ce", "neighbors": "da4518c4d0275970",
        "uniform": "765f300fa65dd0bd", "transpose": "58af5d7d320afc14",
        "next_hop0": "528762ea2f46f72d", "next_port0": "0b02b09dea51effe",
        "next_hop1": "5088f566e25dccdd", "next_port1": "19a5bbf1a29287b5",
    },
    "torus4": {
        "coords": "637b815c3e30f06c", "channels": "9770d52a2db1d9a5",
        "channel_ports": "9a4d08f218fe221d", "neighbors": "c3b6db2327bb8a0d",
        "uniform": "8a7301fb25c3534f", "transpose": "cbb1ff644b83d7e3",
        "next_hop0": "7a4598a72554a25a", "next_port0": "ccfcb1d619c7ec12",
        "walk0": "6691c8be01011681", "next_hop1": "6b5e56fa7143dcd4",
        "next_port1": "f47c50760ca9a8dc", "walk1": "00e70fb31ebf780c",
    },
    "torus16": {
        "coords": "0a8cb9d60d1e7386", "channels": "3945a9a4df790f5a",
        "channel_ports": "3fce3630f6c0e340", "neighbors": "c3ab47c3898af8a2",
        "uniform": "508b829ae88a9f34", "transpose": "a23e9f41ed631cdb",
        "next_hop0": "3cc537e0ccb1d844", "next_port0": "76fca22938d52952",
        "walk0": "10abed6135e1381a", "next_hop1": "717d92fea6c77e0b",
        "next_port1": "7d059751dc55c1a2", "walk1": "2e560eccd298217b",
    },
}


def _hash(a) -> str:
    a = np.ascontiguousarray(a)
    head = str((a.dtype.str, a.shape)).encode()
    return hashlib.sha256(head + a.tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(FROZEN_2D))
def test_2d_outputs_unchanged(name):
    from qsbench.generator import pattern_matrix
    from qsbench.ref.grid import make_grid

    kind = name.rstrip("0123456789")
    side = int(name[len(kind):])
    g = make_grid(kind, (side, side))
    assert g.orders == ((0, 1), (1, 0))
    ch = g.channels()
    got = dict(coords=g.coords, channels=ch,
               channel_ports=g.channel_ports(ch), neighbors=g.neighbors(ch),
               uniform=pattern_matrix(g, "uniform"),
               transpose=pattern_matrix(g, "transpose"))
    for i, o in enumerate(g.orders):
        got[f"next_hop{i}"] = g.next_hop(o)
        got[f"next_port{i}"] = g.next_port(o, ch)
        if f"walk{i}" in FROZEN_2D[name]:
            got[f"walk{i}"] = g.walk(o)
    assert {k: _hash(v) for k, v in got.items()} == FROZEN_2D[name]


@pytest.mark.parametrize("dims", [(4, 4), (5, 3), (16, 16), (4, 4, 4),
                                  (3, 4, 5)])
def test_grid_matches_program_torus(dims):
    """Nodes, channels, output ports, neighbours and the DOR next hops,
    output ports and routes of both orders, as the program builds them."""
    from qsbench.ref.grid import make_grid
    from repro.core import torus
    from repro.core.routes import (dimension_orders, next_hop_table,
                                   next_port_table, walk_routes)

    topo = torus(*dims)
    g = make_grid("torus", dims)
    assert g.n == topo.num_nodes and g.num_ports == topo.num_ports
    assert g.port_local == topo.port_local
    assert np.array_equal(g.coords, topo.coords)
    assert np.array_equal(g.strides, topo.coord_strides)
    ch = g.channels()
    assert np.array_equal(ch, topo.channels)
    assert np.array_equal(g.channel_ports(ch), topo.channel_port)
    assert np.array_equal(g.neighbors(ch), topo.neighbor_table)
    assert g.orders == tuple(dimension_orders(len(dims), binary_only=True))
    for o in g.orders:
        assert np.array_equal(g.next_hop(o), next_hop_table(topo, o))
        assert np.array_equal(g.next_port(o, ch), next_port_table(topo, o))
        assert np.array_equal(g.walk(o), walk_routes(topo, o))


def test_transpose_reverses_coordinates_in_3d():
    from qsbench.generator import pattern_matrix
    from qsbench.ref.grid import make_grid

    g = make_grid("torus", (3, 3, 3))
    t = pattern_matrix(g, "transpose")
    c = g.coords
    for s, d in zip(*np.nonzero(t)):
        assert tuple(c[d]) == tuple(c[s][::-1])
    # every node but the 9 coordinate palindromes sends
    assert (t.sum(1) > 0).sum() == 27 - 9
    with pytest.raises(ValueError):
        pattern_matrix(make_grid("torus", (4, 4, 3)), "transpose")


@pytest.mark.parametrize("kind,dims", [("mesh", (4, 4, 4)), ("torus", (4,)),
                                       ("torus", (4, 2, 4)),
                                       ("cube", (4, 4))])
def test_make_grid_refuses(kind, dims):
    from qsbench.ref.grid import make_grid
    with pytest.raises(ValueError):
        make_grid(kind, dims)
