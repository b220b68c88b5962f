"""N-Rank — the evolutionary model of paper §3.2.

Pipeline (all offline, eq. numbers from the paper):

1. possibility sets / weights  (eq. 4–7)   → ``possibility_weights``
2. transfer & draining probabilities (8–9) → ``transition_probabilities``
3. evolution: init (1), iterate (2–3), terminate → ``evolve`` (jax)

The 2D-mesh-specific "minimum rectangle" membership of eq. (4) is
implemented through the topology-agnostic minimal-path predicate::

    ⟨s,d⟩ ∈ P^{u,n}  ⇔  dist(s,u) + 1 + dist(n,d) == dist(s,d)

which is equivalent on meshes (a channel lies inside MinRect(s,d) with a
non-detouring orientation iff it lies on some minimal s→d path) and remains
well-defined on tori / multi-pod graphs where MinRect is not.  Equivalence
on meshes is property-tested against the literal eq. (4) in
``tests/test_core_nrank.py``.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .topology import Topology

__all__ = [
    "NRankResult",
    "possibility_weights",
    "transition_probabilities",
    "evolve",
    "nrank",
    "nrank_channel",
    "joint_possibility",
]

# paper §3.2.1 defaults
W_TH = 0.01
ITER_TH = 100


@dataclasses.dataclass(frozen=True)
class NRankResult:
    """Output of the N-Rank evolution."""

    w_nr: np.ndarray          # (N,) NR-weights — likelihood of heavy load
    w0: np.ndarray            # (N,) initial weights (eq. 1)
    w_final: np.ndarray       # (N,) residual weight at termination
    iterations: int
    p: np.ndarray             # (C,) transfer probability per channel (eq. 8)
    p_drn: np.ndarray         # (C,) draining probability per channel (eq. 9)
    w_possibility: np.ndarray  # (C,) possibility weight W^{u,n} (eq. 5)


def possibility_weights(dist: np.ndarray, traffic: np.ndarray,
                        channels: np.ndarray,
                        chunk: int = 256) -> tuple[np.ndarray, np.ndarray]:
    """Possibility weights ``W`` (eq. 5) and draining weights ``W_drn``
    (eq. 7) for every channel.

    Args:
      dist: (N, N) hop distances.
      traffic: (N, N) traffic matrix T.
      channels: (C, 2) directed channels (u, n).
      chunk: channels processed per vectorized block (memory control).

    Returns:
      (W, W_drn), each (C,) float64.

    This is the O(C·N²) hot spot of N-Rank; ``repro.kernels.possibility``
    provides the Pallas TPU kernel with this function as its oracle.
    """
    dist = np.asarray(dist, dtype=np.int64)
    traffic = np.asarray(traffic, dtype=np.float64)
    c = channels.shape[0]
    w = np.empty(c, dtype=np.float64)
    w_drn = np.empty(c, dtype=np.float64)
    for lo in range(0, c, chunk):
        hi = min(lo + chunk, c)
        us = channels[lo:hi, 0]
        ns = channels[lo:hi, 1]
        # mask[b, s, d] = channel b on a minimal s→d path
        lhs = dist[:, us].T[:, :, None] + 1 + dist[ns, :][:, None, :]
        mask = lhs == dist[None, :, :]
        w[lo:hi] = (mask * traffic[None]).sum(axis=(1, 2))
        # draining: additionally d == n (eq. 6) ⇒ dist(s,u)+1 == dist(s,n)
        drn_mask = (dist[:, us].T + 1) == dist[:, ns].T  # (b, s)
        w_drn[lo:hi] = (drn_mask * traffic[:, ns].T).sum(axis=1)
    return w, w_drn


def transition_probabilities(
        topo: Topology, traffic: np.ndarray,
        w: np.ndarray | None = None,
        w_drn: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Transfer/draining probabilities (eq. 8–9) and dense transition
    matrices for the evolution.

    Returns:
      p:    (C,) transfer probability per channel.
      p_drn:(C,) draining probability per channel.
      A:    (N, N) with A[u, n] = p^{u,n}            (for eq. 3)
      A_drn:(N, N) with A_drn[u, n] = p^{u,n}(1 − p_drn^{u,n})  (for eq. 2)
    """
    if w is None or w_drn is None:
        w, w_drn = possibility_weights(topo.distances, traffic, topo.channels)
    n = topo.num_nodes
    us, ns = topo.channels[:, 0], topo.channels[:, 1]
    denom = np.zeros(n, dtype=np.float64)
    np.add.at(denom, us, w)
    with np.errstate(invalid="ignore", divide="ignore"):
        p = np.where(denom[us] > 0, w / np.maximum(denom[us], 1e-300), 0.0)
        p_drn = np.where(w > 0, w_drn / np.maximum(w, 1e-300), 0.0)
    p_drn = np.clip(p_drn, 0.0, 1.0)
    a = np.zeros((n, n), dtype=np.float64)
    a_drn = np.zeros((n, n), dtype=np.float64)
    a[us, ns] = p
    a_drn[us, ns] = p * (1.0 - p_drn)
    return p, p_drn, a, a_drn


@partial(jax.jit, static_argnames=("iter_th",))
def _evolve_jax(a: jax.Array, a_drn: jax.Array, w0: jax.Array,
                w_th: float, iter_th: int):
    """Eq. (2)–(3) iterated until Σw < w_th or iter ≥ iter_th (jax)."""

    def cond(state):
        w, _, it = state
        return jnp.logical_and(jnp.sum(w) >= w_th, it < iter_th)

    def body(state):
        w, w_nr, it = state
        arrived = w @ a                 # Σ_u w^u p^{u,n}        (eq. 3 term)
        w_nr = w_nr + arrived
        w = w @ a_drn                   # eq. (2)
        return w, w_nr, it + 1

    w, w_nr, it = jax.lax.while_loop(cond, body, (w0, w0, jnp.int32(0)))
    return w, w_nr, it


def evolve(a: np.ndarray, a_drn: np.ndarray, w0: np.ndarray,
           w_th: float = W_TH, iter_th: int = ITER_TH):
    """Run the evolution; returns (w_final, w_nr, iterations).

    ``w0`` is the full initial-weight carry: the quasi-static re-planner
    (:mod:`repro.noc.ctrl`) seeds it with the previous plan's residual
    fixed point on top of eq. (1), so successive plans evolve from the
    load state the old plan left behind instead of from scratch.
    """
    w, w_nr, it = _evolve_jax(jnp.asarray(a), jnp.asarray(a_drn),
                              jnp.asarray(w0), float(w_th), int(iter_th))
    return np.asarray(w), np.asarray(w_nr), int(it)


def initial_weights(traffic: np.ndarray) -> np.ndarray:
    """Eq. (1): w0[n] = Σ_{n'} T[n, n']."""
    return np.asarray(traffic, dtype=np.float64).sum(axis=1)


def joint_possibility(topo: Topology, traffic: np.ndarray,
                      chunk: int = 4096,
                      use_kernel: bool = False) -> np.ndarray:
    """Joint possibility weights for *consecutive* channels.

    ``J[c1, c2]`` (nonzero only when c2 starts where c1 ends) is the total
    traffic that can traverse c1 = (u, n) immediately followed by
    c2 = (n, n') on one minimal path:

        J = Σ_{s,d} T[s,d] · [dist(s,u) + 2 + dist(n',d) == dist(s,d)]

    This is the channel-level tightening of the paper's "routing algorithms
    never take detours" assumption (§3.2.2): a node-level memoryless walk
    can hop u→n→u, which no detour-free packet ever does; conditioning the
    transfer on the incoming channel removes exactly those impossible
    continuations.  Stored dense (C, C) — C is small (≤ ~4N).

    ``use_kernel=True`` routes through the compiled device path
    (:func:`repro.core.plan_fast.joint_possibility_fast` — O(N³) + O(P·N)
    instead of this oracle's O(P·N²)); this host loop is the oracle it is
    property-tested against.
    """
    if use_kernel:
        from .plan_fast import joint_possibility_fast
        return joint_possibility_fast(topo, traffic)
    dist = np.asarray(topo.distances, np.int64)
    t = np.asarray(traffic, np.float64)
    c = topo.num_channels
    chans = topo.channels
    j = np.zeros((c, c), np.float64)
    # enumerate consecutive pairs
    out_of: dict[int, list[int]] = {}
    for ci, (u, n) in enumerate(chans):
        out_of.setdefault(int(u), []).append(ci)
    pairs = []
    for c1, (u, n) in enumerate(chans):
        for c2 in out_of.get(int(n), []):
            n2 = int(chans[c2, 1])
            if n2 != int(u):  # a u→n→u continuation is never minimal anyway
                pairs.append((c1, c2, int(u), n2))
    pairs = np.array(pairs, np.int64).reshape(-1, 4)
    for lo in range(0, len(pairs), chunk):
        blk = pairs[lo:lo + chunk]
        us, n2s = blk[:, 2], blk[:, 3]
        lhs = dist[:, us].T[:, :, None] + 2 + dist[n2s, :][:, None, :]
        mask = lhs == dist[None, :, :]
        j[blk[:, 0], blk[:, 1]] = (mask * t[None]).sum(axis=(1, 2))
    return j


def nrank_channel(topo: Topology, traffic: np.ndarray,
                  w_th: float = W_TH, iter_th: int = ITER_TH,
                  w0: np.ndarray | None = None,
                  use_kernel: bool = False) -> NRankResult:
    """N-Rank with channel-level evolution state (primary interpretation).

    Identical workflow to §3.2 but the evolving weight lives on channels, so
    a quantum of weight can only continue onto channels that share a minimal
    path with the channel it arrived on.  The literal node-level evolution
    (``nrank``) lets weight diffuse into regions real traffic cannot reach
    without detours, which inverts the predicted trend on edge-I/O
    topologies (see EXPERIMENTS.md §Fidelity); this variant restores the
    paper's own reported behaviour (Table 1, Fig. 8) and is what
    ``build_plan`` uses by default.

    ``w0`` (optional, node-level) overrides the eq. (1) initial weights —
    the warm-start carry of the online re-planner.  Channel-level initial
    weights are rescaled per source so each node still splits its initial
    weight over its minimal outgoing channels.

    ``use_kernel=True`` computes the possibility stages (eq. 5/7 and the
    joint) on the compiled device paths instead of the host loops; the
    evolution and aggregation stay as below.  For the fully fused,
    device-resident pipeline use :func:`repro.core.plan_fast.build_plan_fast`.
    """
    traffic = np.asarray(traffic, dtype=np.float64)
    n, c = topo.num_nodes, topo.num_channels
    chans = topo.channels
    us, ns = chans[:, 0], chans[:, 1]
    if use_kernel:
        from repro.kernels.possibility import ops as _pops
        w, w_drn = _pops.possibility_weights(topo.distances, traffic, chans)
        w = np.asarray(w, np.float64)
        w_drn = np.asarray(w_drn, np.float64)
    else:
        w, w_drn = possibility_weights(topo.distances, traffic, chans)
    with np.errstate(invalid="ignore", divide="ignore"):
        p_drn = np.where(w > 0, w_drn / np.maximum(w, 1e-300), 0.0)
    p_drn = np.clip(p_drn, 0.0, 1.0)
    j = joint_possibility(topo, traffic, use_kernel=use_kernel)
    row = j.sum(1)
    with np.errstate(invalid="ignore", divide="ignore"):
        q = np.where(row[:, None] > 0, j / np.maximum(row, 1e-300)[:, None], 0.0)
    # transfer matrix: arrive at n, drain p_drn, continue per q
    m = q * (1.0 - p_drn)[:, None]            # (C, C)
    # initial channel weights: split each source's traffic equally over its
    # minimal outgoing channels per destination
    dist = np.asarray(topo.distances, np.int64)
    # mask[c, d] = channel c on a minimal path from its own source u to d
    mask = (1 + dist[ns, :]) == dist[us, :]
    counts = np.zeros((n, topo.num_nodes), np.float64)
    np.add.at(counts, us, mask.astype(np.float64))
    share = np.where(mask, traffic[us, :], 0.0)
    denom = counts[us, :]
    with np.errstate(invalid="ignore", divide="ignore"):
        w0c = np.where(denom > 0, share / np.maximum(denom, 1e-300), 0.0).sum(1)
    w0_node = initial_weights(traffic)
    if w0 is not None:
        w0_eff = np.asarray(w0, np.float64)
        outdeg = np.bincount(us, minlength=n).astype(np.float64)
        with np.errstate(invalid="ignore", divide="ignore"):
            scale = np.where(w0_node > 0,
                             w0_eff / np.maximum(w0_node, 1e-300), 0.0)
            extra = np.where(w0_node > 0, 0.0, w0_eff)
        w0c = w0c * scale[us] + extra[us] / np.maximum(outdeg[us], 1.0)
        w0_node = w0_eff

    # aggregation matrix: node arrivals from channel weights
    agg = np.zeros((c, n), np.float64)
    agg[np.arange(c), ns] = 1.0

    def cond(state):
        wc, _, it = state
        return jnp.logical_and(jnp.sum(wc) >= w_th, it < iter_th)

    def body(state):
        wc, w_nr, it = state
        w_nr = w_nr + wc @ aggj      # arrivals at nodes this hop (eq. 3)
        wc = wc @ mj                 # drain + continue (eq. 2)
        return wc, w_nr, it + 1

    # fp64 evolution (scoped x64): keeps this oracle and the fused device
    # pipeline (`plan_fast`, fp64 on CPU) within summation-order noise,
    # so tie-tolerance-boundary choice flips cannot separate them.
    with jax.enable_x64(True):
        wc = jnp.asarray(w0c)
        mj = jnp.asarray(m)
        aggj = jnp.asarray(agg)
        wcf, w_nr, it = jax.lax.while_loop(
            cond, body, (wc, jnp.asarray(w0_node), jnp.int32(0)))
    w_final = np.zeros(n)
    np.add.at(w_final, ns, np.asarray(wcf))
    p, p_drn_n, _, _ = transition_probabilities(topo, traffic, w, w_drn)
    return NRankResult(w_nr=np.asarray(w_nr), w0=w0_node, w_final=w_final,
                       iterations=int(it), p=p, p_drn=p_drn_n,
                       w_possibility=w)


def nrank(topo: Topology, traffic: np.ndarray,
          w_th: float = W_TH, iter_th: int = ITER_TH,
          use_kernel: bool = False,
          w0: np.ndarray | None = None) -> NRankResult:
    """Full N-Rank: topology + traffic distribution → NR-weights.

    ``w0`` (optional) replaces the eq. (1) initial weights — the online
    re-planner's warm-start carry (previous plan's residual on top of the
    fresh initial weights).
    """
    traffic = np.asarray(traffic, dtype=np.float64)
    if traffic.shape != (topo.num_nodes,) * 2:
        raise ValueError(
            f"traffic shape {traffic.shape} != {(topo.num_nodes,)*2}")
    if use_kernel:
        from repro.kernels.possibility import ops as _pops
        w, w_drn = _pops.possibility_weights(
            topo.distances, traffic, topo.channels)
        w, w_drn = np.asarray(w, np.float64), np.asarray(w_drn, np.float64)
    else:
        w, w_drn = possibility_weights(topo.distances, traffic, topo.channels)
    p, p_drn, a, a_drn = transition_probabilities(topo, traffic, w, w_drn)
    if w0 is None:
        w0 = initial_weights(traffic)
    else:
        w0 = np.asarray(w0, dtype=np.float64)
    w_final, w_nr, it = evolve(a, a_drn, w0, w_th, iter_th)
    return NRankResult(w_nr=w_nr, w0=w0, w_final=w_final, iterations=it,
                       p=p, p_drn=p_drn, w_possibility=w)
