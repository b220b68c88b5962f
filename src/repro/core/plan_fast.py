"""Device-resident Q-StaR planning pipeline (jit-compiled end to end).

``build_plan`` strings four host-side numpy stages together — possibility
weights (eq. 5–7), the consecutive-channel joint possibility, the
channel-level evolution (eq. 1–3), and BiDOR's eq. 10 route-cost
minimization — with a host round-trip between each.  At ICI-fabric scale
(32×32 / 64×64 tori) the O(C·N²) loops are intractable on the host and the
round-trips dominate even where they are not.  :func:`build_plan_fast` is
the same pipeline as ONE jitted device computation:

* **One possibility pass.**  The per-destination possibility traffic

      V[c, d] = Σ_s T[s,d] · [dist(s,u) + 1 + dist(n,d) == dist(s,d)]

  (channel c = (u, n)) is the only O(C·N²) work in the whole plan, and
  every downstream weight is a cheap contraction of it: eq. 5 is the row
  sum ``W = V·1``, eq. 7 is the gather ``W_drn[c] = V[c, n]`` (the
  draining predicate is the minimal-path predicate at d = n), and — by the
  triangle inequality over the channel edges — the consecutive-channel
  joint possibility factorizes exactly:

      dist(s,u) + 2 + dist(n2,d) == dist(s,d)
        ⇔  ⟨c1 minimal for (s,d)⟩  ∧  dist(n,d) == 1 + dist(n2,d)

  so ``J[c1, c2] = Σ_d V[c1, d] · [dist(n,d) == 1 + dist(n2,d)]`` costs
  O(P·N) instead of O(P·N²) (P ≈ 3C consecutive pairs).  The pass runs as
  the Pallas kernel (:mod:`repro.kernels.possibility`) on backends that
  compile it and as a chunked jnp reduction elsewhere — identical math.

* **Sparse evolution.**  The channel-level transfer matrix is nonzero only
  on the P consecutive pairs, so eq. (2)–(3) iterate with two
  segment-sums per step (O(P)) instead of the dense (C, C) matvec, fused
  with the node aggregation in a single ``lax.while_loop``.

* **Fused BiDOR.**  Eq. 10 route costs and fault feasibility walk the DOR
  next-hop tables on device (``lax.scan`` over the diameter), and the
  tie-tolerant argmin emits the choice table directly — no numpy between
  N-Rank and the bitmap artifact.

Fault-aware replanning reuses the SAME compiled computation: hard-failed
channels are masked (``live``) rather than dropped, with the degraded hop
distances passed as data, so every fault pattern hits the one cached
compilation.  The masked formulation is algebraically identical to
planning on ``Topology.degrade(..., drop=True)`` (down channels carry zero
possibility weight, leave every denominator, and never receive evolution
weight), which property tests assert against the numpy oracle.

Precision policy: ``precision="auto"`` plans in fp64 on CPU (native, and
bit-stable against the fp64 host oracle's choice tables) and fp32 on
TPU/GPU, where BiDOR's tie tolerance (1e-5 relative, vs fp32's ~1e-7
rounding) absorbs the accumulation difference; see EXPERIMENTS.md
§Planner performance.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import NULL_TRACER, span

from .bidor import TIE_TOL, BiDORTable
from .certify import CertificationError, apply_repair, certify_table
from .nrank import ITER_TH, W_TH, NRankResult, initial_weights
from .qstar import QStarPlan
from .routes import dimension_orders, next_hop_table, next_port_table
from .topology import Topology

__all__ = ["build_plan_fast", "build_plans_batched", "plan_statics",
           "joint_possibility_fast", "plan_cache_key"]


def _resolve_precision(precision: str) -> str:
    if precision == "auto":
        return "fp64" if jax.default_backend() == "cpu" else "fp32"
    return precision


def _precision_scope(precision: str):
    """Context manager selecting the accumulation dtype of the fast path."""
    precision = _resolve_precision(precision)
    if precision == "fp64":
        return jax.enable_x64(True)
    if precision != "fp32":
        raise ValueError(f"unknown precision {precision!r}")
    return contextlib.nullcontext()


def _use_pallas_default() -> bool:
    """Compiled Pallas where the backend supports it; chunked jnp else."""
    from repro.kernels.possibility.ops import backend_supports_pallas
    return backend_supports_pallas()


def _v_block(n: int) -> int:
    """Channel-chunk size of the possibility pass: keeps one block's
    (B, N, N) mask around 100 MB."""
    return int(max(8, min(256, (1 << 24) // max(n * n, 1))))


# --------------------------------------------------------------------- #
# per-topology statics (host-built once, cached)
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class PlanStatics:
    """Trace-time constants of one topology: channel/pair indexing, DOR
    next-hop tables, and the jitted plan computation built over them."""

    n: int
    c: int
    npairs: int
    diam: int
    orders: tuple
    us: jnp.ndarray          # (C,) channel sources
    ns: jnp.ndarray          # (C,) channel heads
    pair_c1: jnp.ndarray     # (P,) consecutive-pair first channel
    pair_c2: jnp.ndarray     # (P,) consecutive-pair second channel
    nh: jnp.ndarray          # (O, N, N) DOR next-hop tables
    port_tables: np.ndarray  # (O, N, N) int8, host (BiDOR artifact)
    # the jitted plan computation, vmapped over traffic matrices; single
    # builds run it on a batch of one, so they match batched lanes bit
    # for bit
    core_batched: object
    jvals: object = None     # jitted joint-possibility values (lazy)


_STATICS_CACHE: dict[tuple, PlanStatics] = {}
_DIST_CACHE: dict[tuple, np.ndarray] = {}
_CACHE_CAP = 16


def _topo_key(topo: Topology) -> tuple:
    return (topo.name, topo.dims, topo.wrap, topo.channels.tobytes())


def _consecutive_pairs(channels: np.ndarray, n: int):
    """(c1, c2) channel pairs with head(c1) == src(c2), u-turns excluded.

    ``channels`` is lexicographically sorted (topology construction), so
    the out-channels of node ``v`` are the contiguous run starting at
    ``searchsorted(us, v)``.
    """
    us = channels[:, 0].astype(np.int64)
    ns = channels[:, 1].astype(np.int64)
    c = len(channels)
    outdeg = np.bincount(us, minlength=n)
    start = np.concatenate([[0], np.cumsum(outdeg)])
    reps = outdeg[ns]                          # out-degree at each head
    c1 = np.repeat(np.arange(c), reps)
    pos = np.arange(len(c1)) - np.repeat(np.cumsum(reps) - reps, reps)
    c2 = start[ns[c1]] + pos
    keep = ns[c2] != us[c1]                    # u→n→u is never minimal
    return c1[keep].astype(np.int32), c2[keep].astype(np.int32)


def _possibility_v(dist, t, us, ns, offset: int, block: int,
                   use_pallas: bool):
    """Per-destination possibility traffic V (C, N) — the one O(C·N²)
    pass.  Pallas kernel where it compiles, chunked jnp elsewhere."""
    c = us.shape[0]
    if use_pallas:
        from repro.kernels.possibility.kernel import possibility_v_pallas
        from repro.kernels.possibility.ops import backend_supports_pallas
        du = dist[:, us]                       # (N, C)
        dn = dist[ns, :]                       # (C, N)
        # an explicit use_pallas on a backend with no compiled lowering
        # (CPU debugging) still works — through the interpreter
        return possibility_v_pallas(du, dn, t, dist, offset=offset,
                                    interpret=not backend_supports_pallas())

    pad = (-c) % block
    us_p = jnp.concatenate([us, jnp.zeros(pad, us.dtype)]) if pad else us
    ns_p = jnp.concatenate([ns, jnp.zeros(pad, ns.dtype)]) if pad else ns

    def one_block(ab):
        a, b = ab
        du = dist[:, a].T                      # (B, N)
        dn = dist[b, :]                        # (B, N)
        lhs = du[:, :, None] + offset + dn[:, None, :]   # (B, N, N)
        mask = (lhs == dist[None]).astype(t.dtype)
        return jnp.einsum("bsd,sd->bd", mask, t)         # (B, N)

    v = jax.lax.map(one_block, (us_p.reshape(-1, block),
                                ns_p.reshape(-1, block)))
    return v.reshape(-1, dist.shape[0])[:c]


def _factored_v(dist, t, us, ns, block, use_pallas):
    """V[c, d] — per-destination possibility traffic of every channel.

    The eq. 4 predicate factorizes (triangle inequality over the channel
    edge):  dist(s,u)+1+dist(n,d) == dist(s,d)
      ⇔  [dist(s,u)+dist(u,d) == dist(s,d)]   (u on a minimal path)
       ∧ [dist(u,d) == 1+dist(n,d)]           ((u,n) in d's min-DAG)
    so the only O(N³) work is the channel-free on-path traffic
    OP[u,d] = Σ_s T[s,d]·[dist(s,u)+dist(u,d) == dist(s,d)] — the
    offset-0 instance of the possibility primitive — and V is a gather:
    V[c,d] = dag[c,d]·OP[u_c,d].  A degree-k topology does k× less
    compare work than the direct (C, N, N) reduction.
    """
    idn = jnp.arange(dist.shape[0], dtype=jnp.int32)
    op = _possibility_v(dist, t, idn, idn, 0, block, use_pallas)
    dag = (dist[us, :] == 1 + dist[ns, :]).astype(t.dtype)
    return dag * op[us, :]


def _joint_vals(dist, v, ns, pair_c1, pair_c2):
    """Joint possibility on the consecutive pairs: the same triangle-
    inequality factorization gives
    J[c1,c2] = Σ_d V[c1,d]·[dist(n,d) == 1+dist(n2,d)] — O(P·N)."""
    n1, n2 = ns[pair_c1], ns[pair_c2]
    jmask = (dist[n1, :] == 1 + dist[n2, :]).astype(v.dtype)
    return (v[pair_c1] * jmask).sum(1)


def _make_core(statics_arrays: dict, n: int, c: int, diam: int,
               block: int, use_pallas: bool):
    """Build the single-plan device computation for one topology."""
    us = statics_arrays["us"]
    ns = statics_arrays["ns"]
    pair_c1 = statics_arrays["pair_c1"]
    pair_c2 = statics_arrays["pair_c2"]
    nh = statics_arrays["nh"]
    seg = jax.ops.segment_sum

    def core(dist, t, w0_eff, use_w0, live, down_pair, w_th, iter_th):
        f = t.dtype
        tiny = jnp.asarray(1e-300 if f == jnp.float64 else 1e-30, f)
        livef = live.astype(f)

        # ---- possibility pass: eq. 5/7 and the joint, all from the
        # factorized V (see _factored_v / _joint_vals) ---- #
        v = _factored_v(dist, t, us, ns, block, use_pallas)
        v = v * livef[:, None]
        w = v.sum(1)                                  # eq. (5)
        w_drn = v[jnp.arange(c), ns]                  # eq. (7): d == n
        jflat = _joint_vals(dist, v, ns, pair_c1, pair_c2) * livef[pair_c2]
        # channel-level transfer values on the consecutive pairs
        rowsum = seg(jflat, pair_c1, num_segments=c)
        p_drn_c = jnp.clip(jnp.where(w > 0, w_drn / jnp.maximum(w, tiny),
                                     0.0), 0.0, 1.0)
        mvals = jnp.where(rowsum[pair_c1] > 0,
                          jflat / jnp.maximum(rowsum[pair_c1], tiny),
                          0.0) * (1.0 - p_drn_c[pair_c1])

        # ---- initial channel weights (eq. 1 split over min channels) -- #
        mask_cd = ((1 + dist[ns, :]) == dist[us, :]) & live[:, None]
        cnt = seg(mask_cd.astype(f), us, num_segments=n)      # (N, N)
        share = mask_cd * t[us, :]
        denom = cnt[us]
        w0c = jnp.where(denom > 0, share / jnp.maximum(denom, tiny),
                        0.0).sum(1)
        w0_base = t.sum(1)                                    # eq. (1)
        outdeg = seg(livef, us, num_segments=n)
        scale = jnp.where(w0_base > 0,
                          w0_eff / jnp.maximum(w0_base, tiny), 0.0)
        extra = jnp.where(w0_base > 0, 0.0, w0_eff)
        w0c_warm = (w0c * scale[us]
                    + extra[us] / jnp.maximum(outdeg[us], 1.0)) * livef
        w0c = jnp.where(use_w0, w0c_warm, w0c * livef)
        w0_node = jnp.where(use_w0, w0_eff, w0_base)

        # ---- evolution: eq. (2)-(3), sparse over consecutive pairs ---- #
        def cond(state):
            wc, _, it = state
            return jnp.logical_and(jnp.sum(wc) >= w_th, it < iter_th)

        def body(state):
            wc, w_nr, it = state
            w_nr = w_nr + seg(wc, ns, num_segments=n)   # arrivals (eq. 3)
            wc = seg(wc[pair_c1] * mvals, pair_c2,
                     num_segments=c)                    # drain+continue
            return wc, w_nr, it + 1

        wcf, w_nr, it = jax.lax.while_loop(
            cond, body, (w0c, w0_node, jnp.int32(0)))
        w_final = seg(wcf, ns, num_segments=n)

        # ---- node-level transfer probabilities (eq. 8-9 diagnostics) -- #
        denom_n = seg(w, us, num_segments=n)
        p = jnp.where(denom_n[us] > 0, w / jnp.maximum(denom_n[us], tiny),
                      0.0)

        # ---- BiDOR: eq. 10 cost walk + fault feasibility, fused ------ #
        dst = jnp.arange(n, dtype=jnp.int32)[None, :]
        cur0 = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None],
                                (n, n))

        def walk(nh_o):
            def step(carry, _):
                cur, acc, ok = carry
                nxt = nh_o[cur, dst]
                moving = nxt != cur
                acc = acc + jnp.where(moving, w_nr[nxt], 0.0)
                ok = ok & ~(moving & down_pair[cur, nxt])
                return (nxt, acc, ok), None

            init = (cur0, jnp.broadcast_to(w_nr[:, None], (n, n)),
                    jnp.ones((n, n), bool))
            (_, acc, ok), _ = jax.lax.scan(step, init, None, length=diam)
            return acc, ok

        per_order = [walk(nh[oi]) for oi in range(nh.shape[0])]
        costs = jnp.stack([a for a, _ in per_order])
        feas = jnp.stack([o for _, o in per_order])
        eye = jnp.eye(n, dtype=bool)
        unroutable = ~feas.any(0) & ~eye
        big = jnp.where(unroutable[None], costs, jnp.inf)
        costs_m = jnp.where(feas, costs, big)
        best = costs_m.min(0)
        tol = TIE_TOL * (1.0 + jnp.abs(best))
        is_min = costs_m <= best + tol
        choice = jnp.where(eye, 0, jnp.argmax(is_min, 0)).astype(jnp.int8)
        return dict(choice=choice, costs=costs_m, unroutable=unroutable,
                    w_nr=w_nr, w0=w0_node, w_final=w_final, it=it,
                    p=p, p_drn=p_drn_c, w=w)

    return core


def plan_statics(topo: Topology, *, binary_only: bool = True,
                 use_pallas: bool | None = None) -> PlanStatics:
    """Host-built trace-time constants for ``build_plan_fast`` (cached per
    topology; bandwidth changes hit the same entry)."""
    if use_pallas is None:
        use_pallas = _use_pallas_default()
    key = _topo_key(topo) + (binary_only, use_pallas)
    hit = _STATICS_CACHE.get(key)
    if hit is not None:
        return hit
    n, c = topo.num_nodes, topo.num_channels
    orders = tuple(map(tuple, dimension_orders(topo.ndim,
                                               binary_only=binary_only)))
    c1, c2 = _consecutive_pairs(topo.channels, n)
    nh = np.stack([next_hop_table(topo, o) for o in orders])
    ports = np.stack([next_port_table(topo, o) for o in orders])
    # route horizon, not BFS diameter: the eq. 10 walk follows DOR routes,
    # whose length express shortcuts may leave above the BFS distances
    diam = topo.route_horizon
    arrays = dict(
        us=jnp.asarray(topo.channels[:, 0].astype(np.int32)),
        ns=jnp.asarray(topo.channels[:, 1].astype(np.int32)),
        pair_c1=jnp.asarray(c1), pair_c2=jnp.asarray(c2),
        nh=jnp.asarray(nh.astype(np.int32)),
    )
    core = _make_core(arrays, n, c, diam, _v_block(n), use_pallas)
    statics = PlanStatics(
        n=n, c=c, npairs=len(c1), diam=diam, orders=orders,
        us=arrays["us"], ns=arrays["ns"],
        pair_c1=arrays["pair_c1"], pair_c2=arrays["pair_c2"],
        nh=arrays["nh"], port_tables=ports,
        core_batched=jax.jit(jax.vmap(
            core, in_axes=(None, 0, 0, 0, None, None, None, None))),
    )
    if len(_STATICS_CACHE) >= _CACHE_CAP:
        _STATICS_CACHE.pop(next(iter(_STATICS_CACHE)))
    _STATICS_CACHE[key] = statics
    return statics


def _down_ids(topo: Topology, down_channels) -> np.ndarray:
    if down_channels is None:
        return np.zeros(0, np.int64)
    down = np.asarray(down_channels)
    if down.dtype == bool:
        return np.nonzero(down)[0]
    return np.unique(down.astype(np.int64))


def _distances_for(topo: Topology, down: np.ndarray) -> np.ndarray:
    """Hop distances of the graph minus the down channels (cached)."""
    if down.size == 0:
        return topo.distances
    key = (_topo_key(topo), down.tobytes())
    hit = _DIST_CACHE.get(key)
    if hit is None:
        hit = topo.degrade(down, drop=True).distances
        if len(_DIST_CACHE) >= _CACHE_CAP:
            _DIST_CACHE.pop(next(iter(_DIST_CACHE)))
        _DIST_CACHE[key] = hit
    return hit


def _fault_arrays(topo: Topology, statics: PlanStatics, down_channels):
    """The masked-fault plan inputs shared by the single and batched
    builders: (down ids, degraded distances, live mask, down node-pair
    mask)."""
    down = _down_ids(topo, down_channels)
    dist = _distances_for(topo, down)
    live = np.ones(statics.c, bool)
    live[down] = False
    down_pair = np.zeros((statics.n, statics.n), bool)
    if down.size:
        down_pair[topo.channels[down, 0], topo.channels[down, 1]] = True
    return down, dist, live, down_pair


def _assemble_plan(topo: Topology, traffic: np.ndarray, statics: PlanStatics,
                   out: dict, have_down: bool) -> QStarPlan:
    unroutable = np.asarray(out["unroutable"]) if have_down else None
    nr = NRankResult(
        w_nr=np.asarray(out["w_nr"], np.float64),
        w0=np.asarray(out["w0"], np.float64),
        w_final=np.asarray(out["w_final"], np.float64),
        iterations=int(out["it"]),
        p=np.asarray(out["p"], np.float64),
        p_drn=np.asarray(out["p_drn"], np.float64),
        w_possibility=np.asarray(out["w"], np.float64))
    table = BiDORTable(
        choice=np.asarray(out["choice"], np.int8), orders=statics.orders,
        costs=np.asarray(out["costs"], np.float64),
        port_tables=statics.port_tables, unroutable=unroutable)
    return QStarPlan(topology=topo, traffic=np.asarray(traffic), nrank=nr,
                     table=table)


def gate_plan(topo: Topology, plan: QStarPlan, *, tracer=None,
              label: str = "") -> QStarPlan:
    """Mandatory deadlock-freedom gate on every plan-producing path.

    Certifies the plan's table (``repro.core.certify``), attaches the
    certificate to the returned plan (``plan.cert``), folds a
    turn-prohibition repair back into the table when the certifier had
    to intervene, and raises :class:`CertificationError` when cycles
    survive repair — a rejected table must never reach a simulator or a
    cache.  Clean plans pass through bit-unchanged.
    """
    cert = certify_table(topo, plan.table, traffic=plan.traffic,
                         w_nr=plan.nrank.w_nr, tracer=tracer, label=label)
    if not cert.ok:
        raise CertificationError(
            f"plan for {topo.name} failed deadlock certification "
            f"({cert.cyclic_nodes} cyclic CDG nodes survive repair; "
            f"label={label!r})")
    if cert.verdict == "repaired":
        plan = dataclasses.replace(plan,
                                   table=apply_repair(plan.table, cert))
    return dataclasses.replace(plan, cert=cert)


def plan_cache_key(topo: Topology, traffic, *, down_channels=None,
                   k_orders: bool = False, w_th: float = W_TH,
                   iter_th: int = ITER_TH,
                   precision: str = "auto") -> str:
    """The content key a cold ``build_plan_fast`` call with these
    arguments uses against a :class:`repro.core.plan_cache.PlanCache` —
    callers that pre-screen the cache (the campaign executor) must key
    identically, including precision resolution."""
    from .plan_cache import plan_key
    return plan_key(topo, traffic, down_channels=down_channels,
                    k_orders=k_orders, w_th=w_th, iter_th=iter_th,
                    precision=_resolve_precision(precision))


def _cache_lookup(cache, topo, traffic, down_channels, k_orders, w_th,
                  iter_th, precision, w0):
    """(key, hit) for the persistent plan cache; (None, None) when the
    build is uncacheable (warm-started) or no cache is in play."""
    if cache is None or w0 is not None:
        return None, None
    key = plan_cache_key(topo, traffic, down_channels=down_channels,
                         k_orders=k_orders, w_th=w_th, iter_th=iter_th,
                         precision=precision)
    return key, cache.get(key, topo)


def build_plan_fast(topo: Topology, traffic: np.ndarray, *,
                    k_orders: bool = False,
                    w_th: float = W_TH, iter_th: int = ITER_TH,
                    w0: np.ndarray | None = None,
                    down_channels=None,
                    precision: str = "auto",
                    use_pallas: bool | None = None,
                    cache=None, tracer=None) -> QStarPlan:
    """Device-resident Q-StaR pipeline — ``build_plan(mode="channel")``
    as one jitted call (possibility → joint → evolution → BiDOR, no host
    round-trips).

    Semantics match :func:`repro.core.qstar.build_plan` with
    ``mode="channel"``, including the warm-start ``w0`` carry and
    fault-aware planning: ``down_channels`` masks the failed channels out
    of both the possibility sets (via degraded hop distances, computed
    host-side and passed as data so every fault pattern reuses the one
    compiled plan) and the eq. 10 minimization; ``table.unroutable``
    flags pairs no dimension order can serve.

    ``cache`` is an optional :class:`repro.core.plan_cache.PlanCache`:
    cold (``w0``-less) builds are served from / stored into it by content
    key, skipping the device computation entirely on a hit.

    ``tracer`` (:mod:`repro.obs.trace`) records the build as a
    ``build_plan_fast`` span holding ``plan_statics``, ``plan_device``,
    ``plan_assemble`` and the gate's ``certify``, and cache hits as
    instants.
    """
    tracer = tracer if tracer is not None else NULL_TRACER
    key, hit = _cache_lookup(cache, topo, traffic, down_channels,
                             k_orders, w_th, iter_th, precision, w0)
    if hit is not None:
        tracer.instant("plan_cache_hit", cat="plan",
                       args={"nodes": topo.num_nodes})
        cert = cache.get_cert(key)
        if cert is not None and cert.verdict == "clean":
            # admission gate satisfied by the stored certificate
            return dataclasses.replace(hit, cert=cert)
        # pre-certifier entry (or a stored repair): re-run the gate
        return gate_plan(topo, hit, tracer=tracer, label="cache_hit")
    warm = w0 is not None
    with span(tracer, "build_plan_fast", cat="plan", nodes=topo.num_nodes,
              warm=warm) as a:
        with span(tracer, "plan_statics", cat="plan") as sa:
            statics = plan_statics(topo, binary_only=not k_orders,
                                   use_pallas=use_pallas)
            down, dist, live, down_pair = _fault_arrays(topo, statics,
                                                        down_channels)
            sa["faults"] = a["faults"] = int(down.size)
            sa.update(ndim=topo.ndim, orders=len(statics.orders))
        if cache is not None:
            cache.stats.device_builds += 1
        with span(tracer, "plan_device", cat="plan", warm=warm), \
                _precision_scope(precision):
            t = jnp.asarray(np.asarray(traffic, np.float64))
            w0_eff = jnp.asarray(np.asarray(
                initial_weights(traffic) if w0 is None else w0,
                np.float64))
            out = statics.core_batched(jnp.asarray(dist), t[None],
                                       w0_eff[None], jnp.asarray([warm]),
                                       jnp.asarray(live),
                                       jnp.asarray(down_pair),
                                       jnp.asarray(float(w_th)),
                                       jnp.int32(iter_th))
            out = {k: v[0] for k, v in jax.device_get(out).items()}
        with span(tracer, "plan_assemble", cat="plan"):
            plan = _assemble_plan(topo, traffic, statics, out,
                                  bool(down.size))
        plan = gate_plan(topo, plan, tracer=tracer, label="build_plan_fast")
    if key is not None:
        cache.put(key, plan, k_orders=k_orders, cert=plan.cert)
    return plan


def build_plans_batched(topo: Topology, traffics, *,
                        w0s=None,
                        k_orders: bool = False,
                        w_th: float = W_TH, iter_th: int = ITER_TH,
                        down_channels=None,
                        precision: str = "auto",
                        use_pallas: bool | None = None,
                        cache=None, tracer=None) -> list[QStarPlan]:
    """Plans for many traffic matrices on one topology in a single vmapped
    device call — the campaign's (pattern, scenario) axis.  Each returned
    plan is identical to its ``build_plan_fast`` equivalent (vmapped
    ``while_loop`` lanes freeze once their own termination hits).

    ``down_channels`` (one fault pattern shared by the whole batch, e.g. a
    ``fault_region_mesh``'s dead channels) masks the failed channels out of
    every plan exactly as in :func:`build_plan_fast`.

    ``cache`` serves/stores cold lanes by content key (see
    :func:`build_plan_fast`); when every lane hits, no device computation
    runs at all.  ``tracer`` records the batched build as a span and
    per-lane cache hits/misses as instants.
    """
    tracer = tracer if tracer is not None else NULL_TRACER
    statics = plan_statics(topo, binary_only=not k_orders,
                           use_pallas=use_pallas)
    down, dist, live, down_pair = _fault_arrays(topo, statics,
                                                down_channels)
    tms = [np.asarray(t, np.float64) for t in traffics]
    if w0s is None:
        w0s = [None] * len(tms)
    if cache is not None:
        cached: dict[int, QStarPlan] = {}
        keys: dict[int, str] = {}
        for i, (tm, w0) in enumerate(zip(tms, w0s)):
            key, hit = _cache_lookup(cache, topo, tm, down_channels,
                                     k_orders, w_th, iter_th, precision,
                                     w0)
            if hit is not None:
                cert = cache.get_cert(key)
                if cert is not None and cert.verdict == "clean":
                    hit = dataclasses.replace(hit, cert=cert)
                else:
                    hit = gate_plan(topo, hit, tracer=tracer,
                                    label=f"cache_hit:{i}")
                cached[i] = hit
                tracer.instant("plan_cache_hit", cat="plan",
                               args={"lane": i, "nodes": topo.num_nodes})
            elif key is not None:
                keys[i] = key
                tracer.instant("plan_cache_miss", cat="plan",
                               args={"lane": i, "nodes": topo.num_nodes})
        if len(cached) < len(tms):
            need = [i for i in range(len(tms)) if i not in cached]
            built = build_plans_batched(
                topo, [tms[i] for i in need],
                w0s=[w0s[i] for i in need], k_orders=k_orders,
                w_th=w_th, iter_th=iter_th, down_channels=down_channels,
                precision=precision, use_pallas=use_pallas,
                tracer=tracer)
            for i, plan in zip(need, built):
                cached[i] = plan
                if i in keys:
                    cache.put(keys[i], plan, k_orders=k_orders,
                              cert=plan.cert)
            cache.stats.device_builds += 1
        return [cached[i] for i in range(len(tms))]
    n = statics.n
    # the single-plan chunking budgets ~one (block, N, N) mask; a vmapped
    # batch multiplies that by its lane count, so large batches advance
    # in slices that keep the peak working set bounded
    group = max(1, (1 << 26) // max(_v_block(n) * n * n, 1))
    plans = []
    with span(tracer, "build_plans_batched", cat="plan",
              nodes=topo.num_nodes, lanes=len(tms), faults=int(down.size)), \
            _precision_scope(precision):
        for lo in range(0, len(tms), group):
            tms_g, w0s_g = tms[lo:lo + group], w0s[lo:lo + group]
            t_b = jnp.asarray(np.stack(tms_g))
            w0_b = jnp.asarray(np.stack(
                [initial_weights(t) if w0 is None
                 else np.asarray(w0, np.float64)
                 for t, w0 in zip(tms_g, w0s_g)]))
            use_b = jnp.asarray(np.array([w0 is not None for w0 in w0s_g]))
            out = jax.device_get(statics.core_batched(
                jnp.asarray(dist), t_b, w0_b, use_b,
                jnp.asarray(live), jnp.asarray(down_pair),
                jnp.asarray(float(w_th)), jnp.int32(iter_th)))
            for i, tm in enumerate(tms_g):
                lane = {k: np.asarray(v)[i] for k, v in out.items()}
                plan = _assemble_plan(topo, tm, statics, lane,
                                      have_down=bool(down.size))
                plans.append(gate_plan(topo, plan, tracer=tracer,
                                       label="build_plans_batched"))
    return plans


def joint_possibility_fast(topo: Topology, traffic: np.ndarray,
                           precision: str = "auto",
                           use_pallas: bool | None = None) -> np.ndarray:
    """Device path for :func:`repro.core.nrank.joint_possibility`: the
    dense (C, C) consecutive-channel joint weights via the V-contraction
    (O(C·N²) + O(P·N) instead of O(P·N²))."""
    statics = plan_statics(topo, use_pallas=use_pallas)
    if statics.jvals is None:
        if use_pallas is None:
            use_pallas = _use_pallas_default()
        block = _v_block(statics.n)

        def jvals(dist, t):
            v = _factored_v(dist, t, statics.us, statics.ns, block,
                            use_pallas)
            return _joint_vals(dist, v, statics.ns, statics.pair_c1,
                               statics.pair_c2)

        statics.jvals = jax.jit(jvals)   # cached with the topology statics
    jvals = statics.jvals
    with _precision_scope(precision):
        flat = np.asarray(jax.device_get(jvals(
            jnp.asarray(topo.distances),
            jnp.asarray(np.asarray(traffic, np.float64)))), np.float64)
    j = np.zeros((statics.c, statics.c), np.float64)
    j[np.asarray(statics.pair_c1), np.asarray(statics.pair_c2)] = flat
    return j
