"""Plain reference of the fabrics the benchmark runs: 2-D meshes and tori
of any number of dimensions, with unit-step links, their port
numbering, hop distances and dimension-order (DOR) routes.

Written for the benchmark and importing nothing of the program under
test.  The numbering follows the simulator's published conventions, so
that tables built here and there can be compared entry by entry:

* node id = Σ_k x_k·stride_k with dimension 0 fastest (x + W·y in 2-D,
  x + W·y + W·H·z in 3-D);
* channels are the directed links (u, n), sorted lexicographically;
* output port 2k is the +k direction, 2k+1 the −k direction, and the last
  port (2·ndim) is local inject/eject;
* on a wrapping dimension the DOR step takes the shorter way round, the
  + way when both are equally short;
* the DOR orders are the ascending and the descending one: order 0 takes
  dimension 0 first (XY in 2-D), order 1 the last dimension first (YX).
"""

from __future__ import annotations

import dataclasses

import numpy as np

# hop distance of a pair no path connects (the simulator's convention)
UNREACHABLE = np.iinfo(np.int32).max // 4


@dataclasses.dataclass(frozen=True)
class Grid:
    """A mesh (``wrap`` False) or torus (``wrap`` True)."""

    dims: tuple
    wrap: bool

    @property
    def n(self) -> int:
        return int(np.prod(self.dims))

    @property
    def ndim(self) -> int:
        return len(self.dims)

    @property
    def port_local(self) -> int:
        return 2 * self.ndim

    @property
    def num_ports(self) -> int:
        return 2 * self.ndim + 1

    @property
    def strides(self) -> np.ndarray:
        return np.cumprod((1,) + self.dims[:-1]).astype(np.int64)

    @property
    def coords(self) -> np.ndarray:
        """(N, ndim) coordinates of every node id."""
        return np.stack(np.unravel_index(np.arange(self.n), self.dims,
                                         order="F"), -1).astype(np.int64)

    @property
    def orders(self) -> tuple:
        """The DOR orders routes are planned over: ascending and
        descending dimension order, ((0, 1), (1, 0)) in 2-D."""
        up = tuple(range(self.ndim))
        return up, up[::-1]

    @property
    def horizon(self) -> int:
        """Longest DOR route, in hops."""
        return sum(d // 2 if self.wrap else d - 1 for d in self.dims)

    def channels(self) -> np.ndarray:
        """(C, 2) directed links (u, n), sorted."""
        c = self.coords
        out = []
        for k in range(self.ndim):
            for step in (1, -1):
                nc = c.copy()
                nc[:, k] += step
                if self.wrap:
                    ok = np.ones(self.n, bool)
                    nc[:, k] %= self.dims[k]
                else:
                    ok = (nc[:, k] >= 0) & (nc[:, k] < self.dims[k])
                ids = nc @ self.strides
                out.append(np.stack([np.arange(self.n)[ok], ids[ok]], -1))
        ch = np.concatenate(out)
        return ch[np.lexsort((ch[:, 1], ch[:, 0]))].astype(np.int64)

    def channel_ports(self, channels: np.ndarray) -> np.ndarray:
        """(C,) output port of each channel at its source."""
        c = self.coords
        delta = c[channels[:, 1]] - c[channels[:, 0]]
        k = np.argmax(delta != 0, axis=1)
        step = delta[np.arange(len(channels)), k]
        size = np.asarray(self.dims)[k]
        if self.wrap:
            step = np.where(np.abs(step) == size - 1, -np.sign(step), step)
        return np.where(step > 0, 2 * k, 2 * k + 1)

    def neighbors(self, channels: np.ndarray) -> np.ndarray:
        """(N, P) neighbour on each output port; −1 if none; self on the
        local port."""
        tab = np.full((self.n, self.num_ports), -1, np.int64)
        tab[channels[:, 0], self.channel_ports(channels)] = channels[:, 1]
        tab[:, self.port_local] = np.arange(self.n)
        return tab

    def distances(self, channels: np.ndarray, live=None) -> np.ndarray:
        """(N, N) int32 hop distances over the live channels, by BFS."""
        live = np.ones(len(channels), bool) if live is None else live
        n = self.n
        adj = np.zeros((n, n), np.float32)
        adj[channels[live, 0], channels[live, 1]] = 1.0
        dist = np.full((n, n), UNREACHABLE, np.int32)
        np.fill_diagonal(dist, 0)
        reach = np.eye(n, dtype=bool)
        frontier = np.eye(n, dtype=np.float32)
        d = 0
        while True:
            d += 1
            nxt = ((frontier @ adj) > 0) & ~reach
            if not nxt.any():
                return dist
            dist[nxt] = d
            reach |= nxt
            frontier = nxt.astype(np.float32)

    def next_hop(self, order) -> np.ndarray:
        """(N, N) next node of the DOR route (cur, dst); dst at dst."""
        c = self.coords
        cur, dst = c[:, None, :], c[None, :, :]
        nxt = np.broadcast_to(cur, (self.n, self.n, self.ndim)).copy()
        moved = np.zeros((self.n, self.n), bool)
        for k in order:
            size = self.dims[k]
            if self.wrap:
                fwd = (dst[..., k] - cur[..., k]) % size
                bwd = (cur[..., k] - dst[..., k]) % size
                step = np.where(fwd == 0, 0, np.where(fwd <= bwd, 1, -1))
            else:
                step = np.sign(dst[..., k] - cur[..., k])
            take = ~moved & (step != 0)
            nxt[..., k] = np.where(take, (nxt[..., k] + step) % size,
                                   nxt[..., k])
            moved |= take
        return (nxt @ self.strides).astype(np.int64)

    def next_port(self, order, channels: np.ndarray) -> np.ndarray:
        """(N, N) output port of the DOR next hop; local port at dst."""
        nh = self.next_hop(order)
        neigh = self.neighbors(channels)
        ports = np.full((self.n, self.n), self.port_local, np.int64)
        here = np.arange(self.n)[:, None]
        for p in range(self.num_ports - 1):
            ports[(nh == neigh[:, p][:, None]) & (nh != here)] = p
        return ports

    def walk(self, order) -> np.ndarray:
        """(N, N, H+1) node sequence of every DOR route, padded with the
        destination."""
        nh = self.next_hop(order)
        seq = np.empty((self.n, self.n, self.horizon + 1), np.int64)
        cur = np.broadcast_to(np.arange(self.n)[:, None],
                              (self.n, self.n)).copy()
        dst = np.broadcast_to(np.arange(self.n)[None, :], (self.n, self.n))
        seq[..., 0] = cur
        for h in range(1, self.horizon + 1):
            cur = nh[cur, dst]
            seq[..., h] = cur
        return seq


def make_grid(kind: str, dims) -> Grid:
    """A 2-D mesh, or a torus of 2 or more dimensions; every side >= 3."""
    if kind not in ("mesh", "torus"):
        raise ValueError(f"unknown fabric {kind!r}")
    ndim_ok = len(dims) == 2 if kind == "mesh" else len(dims) >= 2
    if not ndim_ok or min(dims) < 3:
        raise ValueError(f"a 2-D mesh or a torus of 2 or more dimensions, "
                         f"every side >= 3, is needed; got {kind} {dims}")
    return Grid(tuple(int(d) for d in dims), kind == "torus")
