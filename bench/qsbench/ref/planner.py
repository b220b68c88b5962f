"""Plain reference of the Q-StaR planner: N-Rank (channel-level
evolution, paper §3.2) → BiDOR's eq. 10 choice between the ascending
and the descending dimension order (XY and YX in 2-D) →
BiDOR-G's greedy max-link-load refinement.

Written for the benchmark, importing nothing of the program under test.
It runs in ``jax.numpy`` on the host CPU, in float64 for the reference;
the same code in bfloat16 is the precision control (see ``PERF.md``).

The possibility pass uses the minimal-path factorization: a channel
c = (u, n) lies on a minimal s→d path iff u does and n is one hop
closer to d, so V[c, d] = [dist(u,d) == 1 + dist(n,d)] · OP[u, d] with
OP[u, d] = Σ_s T[s,d]·[dist(s,u) + dist(u,d) == dist(s,d)].
Failed channels are masked (``live``) and the hop distances are those
of the surviving graph.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from .grid import Grid

W_TH = 0.01      # evolution stops once the channel weight falls below
ITER_TH = 100    # ... or after this many iterations (paper §3.2.1)
TIE_TOL = 1e-5   # relative tolerance of eq. 10's tie detection


def _scope(dtype, device=None):
    """The device (the host CPU unless given), with 64-bit types where
    the reference needs them."""
    stack = contextlib.ExitStack()
    stack.enter_context(jax.default_device(
        device if device is not None else jax.devices("cpu")[0]))
    if np.dtype(dtype) == np.float64:
        stack.enter_context(jax.enable_x64(True))
    return stack


@jax.jit
def _onpath_block(dist, t, u_blk):
    """OP[u, d] for a block of nodes u."""
    lhs = dist[:, u_blk].T[:, :, None] + dist[u_blk, :][:, None, :]
    mask = (lhs == dist[None]).astype(t.dtype)
    return jnp.einsum("bsd,sd->bd", mask, t)


def consecutive_pairs(channels: np.ndarray, n: int):
    """(c1, c2) channel pairs with head(c1) == source(c2), u-turns out."""
    us, ns = channels[:, 0], channels[:, 1]
    c1, c2 = [], []
    out = [np.flatnonzero(us == v) for v in range(n)]
    for a in range(len(channels)):
        for b in out[ns[a]]:
            if ns[b] != us[a]:
                c1.append(a)
                c2.append(b)
    return np.asarray(c1), np.asarray(c2)


class Planner:
    """Reference plans for one fabric (its statics built once)."""

    def __init__(self, grid: Grid):
        self.grid = grid
        self.channels = grid.channels()
        self.c1, self.c2 = consecutive_pairs(self.channels, grid.n)
        self.nh = np.stack([grid.next_hop(o) for o in grid.orders])
        self._dist = {}

    def distances(self, live: np.ndarray) -> np.ndarray:
        key = live.tobytes()
        if key not in self._dist:
            self._dist = {key: self.grid.distances(self.channels, live)}
        return self._dist[key]

    def plan(self, traffic, *, bw=None, w0=None, dtype=np.float64,
             device=None) -> dict:
        """One plan: ``bw`` is the per-channel bandwidth (0 = failed),
        ``w0`` the warm-start node weights of the online re-planner;
        ``device`` runs it elsewhere than on the host CPU.

        Returns w_nr, w_final, iterations, the masked route ``costs``
        (O, N, N), ``choice`` (N, N), and ``unroutable`` (None on an
        intact fabric).
        """
        n, ch = self.grid.n, self.channels
        c = len(ch)
        bw = np.ones(c) if bw is None else np.asarray(bw, np.float64)
        live = bw > 0
        dist_np = self.distances(live)
        down_pair = np.zeros((n, n), bool)
        down_pair[ch[~live, 0], ch[~live, 1]] = True
        with _scope(dtype, device):
            f = jnp.dtype(dtype)
            tiny = jnp.asarray(1e-300 if f == jnp.float64 else 1e-30, f)
            dist = jnp.asarray(dist_np)
            t = jnp.asarray(np.asarray(traffic, np.float64), f)
            us, ns = jnp.asarray(ch[:, 0]), jnp.asarray(ch[:, 1])
            c1, c2 = jnp.asarray(self.c1), jnp.asarray(self.c2)
            livef = jnp.asarray(live, f)
            seg = jax.ops.segment_sum

            blk = max(1, min(64, (1 << 24) // (n * n)))
            op = jnp.concatenate([
                _onpath_block(dist, t, jnp.arange(lo, min(lo + blk, n)))
                for lo in range(0, n, blk)])
            dag = (dist[us, :] == 1 + dist[ns, :]).astype(f)
            v = dag * op[us, :] * livef[:, None]
            w = v.sum(1)
            w_drn = v[jnp.arange(c), ns]
            jmask = (dist[ns[c1], :] == 1 + dist[ns[c2], :]).astype(f)
            jflat = (v[c1] * jmask).sum(1) * livef[c2]
            rowsum = seg(jflat, c1, num_segments=c)
            p_drn = jnp.clip(jnp.where(w > 0, w_drn / jnp.maximum(w, tiny),
                                       0.0), 0.0, 1.0)
            mvals = jnp.where(rowsum[c1] > 0,
                              jflat / jnp.maximum(rowsum[c1], tiny),
                              0.0) * (1.0 - p_drn[c1])

            # eq. 1, split over each source's minimal outgoing channels
            mask_cd = ((1 + dist[ns, :]) == dist[us, :]) & jnp.asarray(
                live)[:, None]
            cnt = seg(mask_cd.astype(f), us, num_segments=n)
            denom = cnt[us]
            w0c = jnp.where(denom > 0, mask_cd * t[us, :]
                            / jnp.maximum(denom, tiny), 0.0).sum(1)
            w0_base = t.sum(1)
            if w0 is None:
                w0c, w_nr = w0c * livef, w0_base
            else:
                w0e = jnp.asarray(np.asarray(w0, np.float64), f)
                outdeg = seg(livef, us, num_segments=n)
                scale = jnp.where(w0_base > 0,
                                  w0e / jnp.maximum(w0_base, tiny), 0.0)
                extra = jnp.where(w0_base > 0, 0.0, w0e)
                w0c = (w0c * scale[us]
                       + extra[us] / jnp.maximum(outdeg[us], 1.0)) * livef
                w_nr = w0e

            # eq. 2-3: arrivals, then drain and continue
            wc, it = w0c, 0
            while float(wc.sum()) >= W_TH and it < ITER_TH:
                w_nr = w_nr + seg(wc, ns, num_segments=n)
                wc = seg(wc[c1] * mvals, c2, num_segments=c)
                it += 1
            w_final = seg(wc, ns, num_segments=n)

            # eq. 10: cost of every route of each order, feasibility on faults
            dst = jnp.arange(n)[None, :]
            costs, feas = [], []
            for nh_o in self.nh:
                nh = jnp.asarray(nh_o)
                cur = jnp.broadcast_to(jnp.arange(n)[:, None], (n, n))
                acc = jnp.broadcast_to(w_nr[:, None], (n, n))
                ok = jnp.ones((n, n), bool)
                dp = jnp.asarray(down_pair)
                for _ in range(self.grid.horizon):
                    nxt = nh[cur, dst]
                    moving = nxt != cur
                    acc = acc + jnp.where(moving, w_nr[nxt], 0.0)
                    ok = ok & ~(moving & dp[cur, nxt])
                    cur = nxt
                costs.append(acc)
                feas.append(ok)
            costs, feas = jnp.stack(costs), jnp.stack(feas)
            eye = jnp.eye(n, dtype=bool)
            unroutable = ~feas.any(0) & ~eye
            costs = jnp.where(feas, costs,
                              jnp.where(unroutable[None], costs, jnp.inf))
            best = costs.min(0)
            is_min = costs <= best + TIE_TOL * (1.0 + jnp.abs(best))
            choice = jnp.where(eye, 0, jnp.argmax(is_min, 0))
            out = dict(w_nr=w_nr, w_final=w_final, costs=costs,
                       choice=choice, unroutable=unroutable)
            out = {k: np.asarray(jax.device_get(v)).astype(
                np.int8 if k == "choice" else
                bool if k == "unroutable" else np.float64)
                for k, v in out.items()}
        out["iterations"] = it
        if live.all():
            out["unroutable"] = None
        return out


def choice_gap(costs: np.ndarray, choice: np.ndarray,
               unroutable=None) -> float:
    """Widest relative excess of a choice table's route cost over the
    cheapest route, both priced by the reference's weights:
    max over routable pairs of (cost[choice] − best) / (1 + |best|).

    An exact tie costs 0; a pair that BiDOR's own tie tolerance would
    accept costs at most about 1e-5; a choice of an order that crosses a
    failed link costs infinity.
    """
    n = costs.shape[1]
    best = costs.min(0)
    picked = np.take_along_axis(costs, choice.astype(np.int64)[None], 0)[0]
    with np.errstate(invalid="ignore"):
        gap = (picked - best) / (1.0 + np.abs(best))
    keep = ~np.eye(n, dtype=bool)
    if unroutable is not None:
        keep &= ~unroutable
    gap = np.where(keep, gap, 0.0)
    return float(np.nan_to_num(gap, nan=np.inf, posinf=np.inf).max())


# --------------------------------------------------------------------- #
# BiDOR-G: greedy max-link-load refinement (float64 on the host)
# --------------------------------------------------------------------- #
class Refiner:
    """Route sequences of one fabric, for link loads and refinement."""

    def __init__(self, grid: Grid):
        self.grid = grid
        self.channels = grid.channels()
        n = grid.n
        self.seqs = [grid.walk(o) for o in grid.orders]
        self.lut = np.full((n, n), -1, np.int64)
        self.lut[self.channels[:, 0], self.channels[:, 1]] = np.arange(
            len(self.channels))

    def link_load(self, traffic, choice, unroutable, bw) -> np.ndarray:
        """Per-channel load over bandwidth implied by a choice table."""
        load = np.zeros(len(self.channels))
        t = np.asarray(traffic, np.float64)
        if unroutable is not None:
            t = np.where(unroutable, 0.0, t)
        for oi, seq in enumerate(self.seqs):
            w = np.where(choice == oi, t, 0.0)
            for h in range(seq.shape[-1] - 1):
                a, b = seq[..., h], seq[..., h + 1]
                moving = (a != b) & (self.lut[a, b] >= 0)
                if not (a != b).any():
                    break
                np.add.at(load, self.lut[a[moving], b[moving]], w[moving])
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(bw > 0, load / np.where(bw > 0, bw, 1.0),
                            np.where(load > 0, np.inf, 0.0))

    def refine(self, traffic, choice, unroutable, bw,
               sweeps: int) -> np.ndarray:
        """Sweep the pairs in decreasing traffic order and move a pair
        to the other order whenever that lowers the peak load among the
        links it would use."""
        t = np.asarray(traffic, np.float64)
        n = self.grid.n
        bw = np.asarray(bw, np.float64)

        def links(oi, s, d):
            seq = self.seqs[oi][s, d]
            ids = []
            for h in range(len(seq) - 1):
                a, b = int(seq[h]), int(seq[h + 1])
                if a == b:
                    break
                c = int(self.lut[a, b])
                if c < 0:
                    return None
                ids.append(c)
            return ids

        choice = np.array(choice, np.int8, copy=True)
        load = self.link_load(t, choice, unroutable, bw)
        bwe = np.where(bw > 0, bw, 1e-12)
        pairs = [(s, d) for s in range(n) for d in range(n)
                 if s != d and t[s, d] > 0
                 and not (unroutable is not None and unroutable[s, d])]
        pairs.sort(key=lambda p: -t[p])
        for _ in range(sweeps):
            changed = 0
            for s, d in pairs:
                cur = int(choice[s, d])
                cur_links = links(cur, s, d)
                if cur_links is None:
                    continue
                best_oi = cur
                best_peak = max((load[c] for c in cur_links), default=0.0)
                for oi in range(len(self.seqs)):
                    if oi == cur:
                        continue
                    alt = links(oi, s, d)
                    if alt is None:
                        continue
                    peak = 0.0
                    for c in alt:
                        peak = max(peak, load[c] + (
                            0 if c in cur_links else t[s, d] / bwe[c]))
                    if peak < best_peak - 1e-15:
                        best_oi, best_peak = oi, peak
                if best_oi != cur:
                    for c in cur_links:
                        load[c] -= t[s, d] / bwe[c]
                    for c in links(best_oi, s, d):
                        load[c] += t[s, d] / bwe[c]
                    choice[s, d] = best_oi
                    changed += 1
            if changed == 0:
                break
        return choice
