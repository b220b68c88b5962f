"""Quasi-static control plane: fault injection, drift detection, and
online N-Rank re-planning.

Q-StaR's premise (paper §3.1) is *quasi-static* routing: plans are cheap
enough to recompute at a coarse timescale as topology and traffic change.
The simulator alone only replays one offline plan; this module closes the
loop:

* an **event schedule** (:class:`LinkFail` / :class:`LinkRecover` /
  :class:`TrafficDrift`) perturbs a running simulation — link bandwidth
  changes flow through the per-channel gating in :mod:`repro.noc.sim`,
  traffic epochs swap the generation tables;
* an **online estimator** (:class:`TrafficEstimator`) accumulates an
  observed traffic matrix from the per-flow injection counters the
  simulator already tracks, and a **drift detector**
  (:class:`DriftDetector`) watches the always-on per-channel forwarding
  profile for distribution shift;
* a **re-planner** re-runs N-Rank *warm-started from the previous fixed
  point* (``w0`` carry), rebuilds BiDOR against the degraded topology
  (infeasible dimension orders leave the minimization, so every route
  stays a pure DOR route inside its VC class — deadlock-free by
  construction), optionally refines with BiDOR-G against the degraded
  bandwidths, and shedding unroutable pairs at the source (admission
  control);
* the new tables **hot-swap** into the running simulation between chunks
  (:func:`repro.noc.sim.retarget_tables`) without touching in-flight
  state.

Three policies bracket the design space (the ``dynamics`` benchmark):
``"oracle"`` replans instantly from ground truth at every event,
``"stale"`` never replans (the seed repo's behaviour), and ``"online"``
replans from its own estimates when a fault is signalled or drift is
detected.  Adaptive routing (odd-even) runs through the same event
machinery as the per-cycle-reactive contrast.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bidor import BiDORTable, bidor, greedy_refine
from repro.core.certify import (CertificationError, apply_repair,
                                certify_table)
from repro.core.nrank import NRankResult, initial_weights, nrank_channel
from repro.core.plan_fast import build_plan_fast
from repro.core.topology import Topology
from repro.obs.log import EventLog
from repro.obs.probe import Telemetry, resolved_epoch
from repro.obs.trace import NULL_TRACER, span, tagged
from .watchdog import WatchdogReport
from .sim import (build_tables, get_runner, make_states,
                  maybe_shard_states, postprocess, queue_occupancy,
                  retarget_tables, runner_builds, source_queue_meta)
from .simconfig import Algo, SimConfig, SimResult

__all__ = [
    "LinkFail", "LinkRecover", "TrafficDrift", "Scenario",
    "TrafficEstimator", "DriftDetector", "ReplanConfig", "Replan",
    "ControlledResult", "run_controlled",
]


# ---------------------------------------------------------------------- #
# events & scenarios
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class LinkFail:
    """Fail (bw_scale = 0) or degrade (0 < bw_scale < 1) directed channels
    at an absolute cycle.  ``links`` holds (u, n) node pairs; a full
    bidirectional link is two entries."""

    cycle: int
    links: tuple
    bw_scale: float = 0.0


@dataclasses.dataclass(frozen=True)
class LinkRecover:
    """Restore the listed channels to their original bandwidth."""

    cycle: int
    links: tuple


@dataclasses.dataclass(frozen=True)
class TrafficDrift:
    """Swap the generation traffic matrix (a new epoch) and optionally
    scale every lane's injection rate."""

    cycle: int
    traffic: np.ndarray
    rate_scale: float = 1.0


@dataclasses.dataclass(frozen=True)
class Scenario:
    """A named event schedule plus the control policy that faces it.

    ``policy``: "stale" (never replan), "oracle" (replan from ground truth
    at every event), or "online" (replan from observed estimates on fault
    signals and detected drift).  Non-BiDOR algorithms ignore the policy —
    events still apply (they are the environment, not the plan).
    """

    name: str
    events: tuple = ()
    policy: str = "stale"
    replan: "ReplanConfig | None" = None

    def __post_init__(self):
        cycles = [e.cycle for e in self.events]
        if cycles != sorted(cycles):
            raise ValueError("scenario events must be sorted by cycle")
        if any(c <= 0 for c in cycles):
            raise ValueError(
                "event cycles must be >= 1 (events apply at chunk "
                "boundaries after the cycle; bake cycle-0 conditions "
                "into the topology/traffic instead)")
        if self.policy not in ("stale", "oracle", "online"):
            raise ValueError(f"unknown policy {self.policy!r}")


# ---------------------------------------------------------------------- #
# online estimation & drift detection
# ---------------------------------------------------------------------- #
class TrafficEstimator:
    """Observed traffic matrix from the simulator's per-flow counters.

    The simulator stamps every generated packet with a per-(source,
    destination) sequence number (``next_seq``); its per-epoch delta *is*
    the observed pair-count matrix.  An exponential moving average over
    epochs keeps the estimate current under drift while smoothing
    sampling noise — exactly the "statistical information" path of paper
    §4.1, but gathered online.

    ``prior`` is the offline matrix the initial plan was built from: it
    backs :attr:`matrix` until the first packets are observed, so a
    cold-start replan (a fault signalled before any delivery) plans
    from the best statistics available instead of requiring every
    caller to carry its own fallback.  The prior never mixes into the
    EMA — the first observed epoch replaces it outright, exactly as
    before — and an all-zero observation window simply keeps the
    current estimate (the empty-window divide is guarded here, in both
    :meth:`update` and :attr:`matrix`, not at call sites).
    """

    def __init__(self, num_nodes: int, ema: float = 0.5,
                 prior: np.ndarray | None = None):
        self.ema = float(ema)
        self._m: np.ndarray | None = None
        self._n = int(num_nodes)
        self._prior = (np.asarray(prior, np.float64).copy()
                       if prior is not None else None)

    def update(self, pair_counts: np.ndarray) -> None:
        """Fold one epoch's (N, N) pair-count delta into the estimate."""
        c = np.asarray(pair_counts, np.float64)
        if c.shape != (self._n, self._n):
            raise ValueError(f"pair_counts shape {c.shape}")
        tot = c.sum()
        if tot <= 0:
            return
        obs = c / tot
        if self._m is None:
            self._m = obs
        else:
            self._m = (1.0 - self.ema) * self._m + self.ema * obs

    @property
    def matrix(self) -> np.ndarray | None:
        """Current normalized estimate — the observed EMA once any
        packets have been seen, else the offline prior; None only when
        neither carries any demand."""
        m = self._m if self._m is not None else self._prior
        if m is None:
            return None
        m = m.copy()
        np.fill_diagonal(m, 0.0)
        s = m.sum()
        return m / s if s > 0 else None


class DriftDetector:
    """Distribution-shift detector over the per-channel forwarding profile.

    The reference profile is pinned at plan time; each epoch's observed
    profile (always-on ``chan_seen`` deltas, normalized to unit sum) is
    compared by total-variation distance.  Distance above ``threshold``
    flags drift — the re-planner then resets the reference.
    """

    def __init__(self, threshold: float = 0.25):
        self.threshold = float(threshold)
        self._ref: np.ndarray | None = None
        self.last_distance = 0.0

    def reset(self) -> None:
        """Forget the reference (called after a replan)."""
        self._ref = None
        self.last_distance = 0.0

    def update(self, chan_counts: np.ndarray) -> bool:
        """Feed one epoch's per-channel counts; True ⇔ drift detected."""
        c = np.asarray(chan_counts, np.float64)
        tot = c.sum()
        if tot <= 0:
            return False
        prof = c / tot
        if self._ref is None:
            self._ref = prof
            return False
        self.last_distance = 0.5 * float(np.abs(prof - self._ref).sum())
        return self.last_distance > self.threshold


# ---------------------------------------------------------------------- #
# re-planning
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ReplanConfig:
    """Knobs of the online re-planner."""

    epoch: int = 500            # control period (cycles) between checks
    drift_threshold: float = 0.25
    ema: float = 0.5            # estimator smoothing
    warm: bool = True           # carry the previous N-Rank fixed point
    greedy_sweeps: int = 2      # BiDOR-G refinement against degraded bw
    sat_occupancy: float = 0.9  # source-queue fraction flagging saturation
    # hot-swap guard: reject a replan whose shed fraction (unroutable
    # pairs among the pairs with demand) exceeds this, keeping the
    # previous table instead of silently wedging most of the traffic
    max_shed: float = 0.5


@dataclasses.dataclass(frozen=True)
class Replan:
    """One re-planning action (for logs/plots/tests)."""

    cycle: int
    trigger: str                # "fault" | "drift" | "event"
    iterations: int             # N-Rank evolution iterations
    unroutable_pairs: int
    drift_distance: float = 0.0


def replan(topo: Topology, traffic: np.ndarray, channel_bw: np.ndarray,
           prev: "object | None" = None, *,
           warm: bool = True, greedy_sweeps: int = 2,
           use_fast: bool = True, tracer=None,
           ) -> tuple[BiDORTable, "object"]:
    """One quasi-static re-planning step against a degraded fabric.

    Args:
      topo: the intact topology (full channel indexing).
      traffic: the (estimated or true) traffic matrix to plan for.
      channel_bw: current per-channel bandwidth; 0 marks hard-failed
        channels.
      prev: previous :class:`repro.core.nrank.NRankResult` for the
        warm-start carry (its residual fixed point seeds the new
        evolution on top of the fresh eq. (1) weights).
      use_fast: run N-Rank + BiDOR as the single jitted device pipeline
        (:func:`repro.core.plan_fast.build_plan_fast`; hard-failed
        channels are masked, so every fault pattern reuses one
        compilation) instead of the stage-by-stage host oracle.  Both
        produce the same choice tables; the fast path is what makes
        online replanning latency proportional to the device, not the
        host loops.

    Returns (table, nrank_result).  ``table.unroutable`` flags pairs no
    dimension order can serve; shed their generation upstream.
    """
    tracer = tracer if tracer is not None else NULL_TRACER
    bw = np.asarray(channel_bw, np.float64)
    down = np.nonzero(bw <= 0)[0]
    plan_topo = dataclasses.replace(topo, channel_bw=bw)
    w0 = None
    if warm and prev is not None:
        w0 = initial_weights(traffic) + np.asarray(prev.w_final, np.float64)
    if use_fast:
        plan = build_plan_fast(plan_topo, traffic, w0=w0,
                               down_channels=down if down.size else None,
                               tracer=tracer)
        table, nr = plan.table, plan.nrank
    else:
        # N-Rank sees the degraded connectivity (hard-failed channels
        # leave the possibility sets); BiDOR masks them from the choice.
        nr_topo = (plan_topo.degrade(down, drop=True) if down.size
                   else plan_topo)
        nr = nrank_channel(nr_topo, traffic, w0=w0)
        table = bidor(plan_topo, nr.w_nr,
                      down_channels=down if down.size else None)
    if greedy_sweeps > 0:
        with span(tracer, "greedy_refine", cat="ctrl") as a:
            table = greedy_refine(plan_topo, traffic, table,
                                  sweeps=greedy_sweeps, stats=a)
    # deadlock gate on the hot-swap artifact: build_plan_fast certifies
    # its own output, but greedy refinement (and the host-oracle path)
    # re-shape the choice table afterwards — certify what actually ships
    cert = certify_table(plan_topo, table, traffic=traffic, w_nr=nr.w_nr,
                         tracer=tracer, label="replan")
    if not cert.ok:
        raise CertificationError(
            f"replan for {topo.name} failed deadlock certification "
            f"({cert.cyclic_nodes} cyclic CDG nodes survive repair)")
    if cert.verdict == "repaired":
        table = apply_repair(table, cert)
    return table, nr


# ---------------------------------------------------------------------- #
# the controlled run
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class ControlledResult:
    """Output of one controlled (event-driven) run."""

    scenario: str
    policy: str
    points: list                 # [(rate, seed), ...] lane order
    results: list                # [SimResult, ...] per lane
    replans: list                # [Replan, ...]
    # time-resolved load: per lane, the peak over control epochs of the
    # max bandwidth-normalized link load (the completion-time bottleneck
    # metric; a saturated degraded link pins it at ≈ 1)
    link_peak: np.ndarray
    epoch_bounds: list           # [(t0, t1), ...] control epochs
    # in-sim probe rings (cfg.telemetry on), bw-normalized against the
    # bandwidth in effect per telemetry slot (faults tracked)
    telemetry: "Telemetry | None" = None
    # stall-watchdog summary over all lanes (cfg.watchdog on)
    watchdog: "object | None" = None

    def result_with_peak(self, i: int) -> SimResult:
        """Lane i's SimResult with the time-resolved link peak in
        ``link_load_max`` (the static field would normalize by the intact
        bandwidths)."""
        return dataclasses.replace(self.results[i],
                                   link_load_max=float(self.link_peak[i]))


def _apply_events(events, bw, topo, base_bw):
    """Fold one boundary's events into the environment; returns the new
    (bw, traffic, rate_scale, kinds) with traffic/rate None if unchanged."""
    traffic = None
    rate_scale = None
    kinds = set()
    for ev in events:
        if isinstance(ev, LinkFail):
            ids = [topo.channel_index(*l) for l in ev.links]
            bw = bw.copy()
            bw[ids] = base_bw[ids] * ev.bw_scale
            kinds.add("fault")
        elif isinstance(ev, LinkRecover):
            ids = [topo.channel_index(*l) for l in ev.links]
            bw = bw.copy()
            bw[ids] = base_bw[ids]
            kinds.add("fault")
        elif isinstance(ev, TrafficDrift):
            traffic = np.asarray(ev.traffic, np.float64)
            rate_scale = float(ev.rate_scale)
            kinds.add("drift")
        else:
            raise TypeError(f"unknown event {ev!r}")
    return bw, traffic, rate_scale, kinds


def _bw_slots(bw_hist, epoch: int, slots: int, total: int) -> np.ndarray:
    """Per-slot channel bandwidth for telemetry load normalization.

    ``bw_hist`` is [(cycle, bw), ...] — the bandwidth vector in effect
    from each cycle on (faults and recoveries append entries).  A slot is
    normalized by the bw in effect at the END of its last accumulation
    window; when the ring wraps, the later window wins, consistent with
    its counts dominating the accumulated slot.
    """
    out = np.zeros((slots, bw_hist[0][1].shape[0]))
    for j in range(slots):
        last = min(j * epoch + epoch, total) - 1   # slot's last cycle
        t = j * epoch + epoch * slots
        while t < total:                            # ring wraps
            last = min(t + epoch, total) - 1
            t += epoch * slots
        bw = bw_hist[0][1]
        for cyc, b in bw_hist:
            if cyc <= last:
                bw = b
        out[j] = bw
    return out


_NR_FIELDS = ("w_nr", "w0", "w_final", "p", "p_drn", "w_possibility")
# the sim counters the controller reads at every epoch boundary
_COUNTERS = ("next_seq", "chan_seen", "chan_fwd", "meas_cnt")


def _ctrl_snapshot(batched, *, bound_i, sat, link_peak, bw, cur_traffic,
                   cur_gen, cur_unroutable, fault_pending, estimator,
                   detector, replans, table, nr_prev, bw_hist=None):
    """Serializable (arrays, meta) state of a controlled run at the TOP
    of boundary iteration ``bound_i``: everything up to
    ``bounds[bound_i - 1]`` (events, replans, counters) applied, the next
    epoch not yet run.  ``_ctrl_restore`` inverts it bit-identically."""
    arrays = {f"s_{k}": np.asarray(v)
              for k, v in jax.device_get(batched).items()}
    arrays.update(sat=sat, link_peak=link_peak, bw=bw,
                  cur_traffic=cur_traffic, cur_gen=cur_gen)
    if cur_unroutable is not None:
        arrays["cur_unroutable"] = np.asarray(cur_unroutable, bool)
    if estimator._m is not None:
        arrays["est_m"] = estimator._m
    if detector._ref is not None:
        arrays["det_ref"] = detector._ref
    if table is not None:
        arrays["tab_choice"] = np.asarray(table.choice, np.int8)
    if bw_hist:
        arrays["bwh"] = np.stack([b for _, b in bw_hist])
    if nr_prev is not None:
        for f in _NR_FIELDS:
            arrays[f"nr_{f}"] = np.asarray(getattr(nr_prev, f),
                                           np.float64)
    meta = dict(bound_i=int(bound_i),
                bwh_cycles=[int(c) for c, _ in (bw_hist or [])],
                fault_pending=bool(fault_pending),
                last_distance=float(detector.last_distance),
                has_nr=nr_prev is not None,
                nr_iterations=(int(nr_prev.iterations)
                               if nr_prev is not None else 0),
                replans=[dataclasses.asdict(r) for r in replans])
    return arrays, meta


def run_controlled(topo: Topology, traffic: np.ndarray, cfg: SimConfig,
                   scenario: Scenario | None = None, *,
                   rates: list[float] | None = None,
                   seeds: list[int] | None = None,
                   bidor_table: BiDORTable | None = None,
                   nrank0: NRankResult | None = None,
                   sat_occupancy: float | None = None,
                   multi_device: bool | None = None,
                   checkpoint=None,
                   verbose: bool = False,
                   tracer=None) -> ControlledResult:
    """Run a simulation under an event schedule with a control policy.

    Lanes are the (rate, seed) grid, batched exactly as
    :func:`repro.noc.sim.run_sweep` (same per-point PRNG streams): with an
    empty scenario the chunked, hot-swapping loop is bit-identical to the
    single-call sweep (asserted by ``tests/test_ctrl.py``).
    ``multi_device`` selects the ``shard_map`` lane-parallel runner for
    every control epoch (semantics as in
    :func:`repro.noc.sim.get_runner`); the per-cycle transition itself —
    fused kernel vs. unfused jnp — follows ``cfg.use_kernel``, and both
    knobs leave every statistic bit-identical.

    The run advances in control epochs (``scenario.replan.epoch`` cycles,
    event cycles added as extra boundaries).  At each boundary the
    environment applies due events, the controller reads the on-device
    counters, and — policy permitting — re-plans and hot-swaps tables.

    ``checkpoint`` — optional epoch-boundary checkpointer (duck-typed:
    ``save(arrays, meta)`` persists a flat ``dict[str, np.ndarray]`` plus
    a JSON-able meta dict; ``load()`` returns the latest such pair or
    None).  At the top of every boundary the full run state (sim pytree,
    environment, estimator/detector, warm-start fixed point, replan log)
    is saved; on entry a stored snapshot is restored and the completed
    epochs skipped.  The boundary grid is deterministic, so the resumed
    run replays the identical chunk lengths (same cached compilations)
    and its results are bit-identical to the uninterrupted run
    (``tests/test_service.py``).

    ``tracer`` — optional tracer (:mod:`repro.obs.trace`, whose
    docstring lists the spans); when present the loop emits ctrl-plane
    events (epoch and boundary spans, drift scores, detection firings,
    environment events, replan spans with their planner, BiDOR-G,
    certifier and hot-swap children).  Epoch spans block on device
    completion to time real work, so tracing perturbs wall time but
    never results.
    """
    tracer = tracer if tracer is not None else NULL_TRACER
    log = EventLog(verbose=verbose)
    scenario = scenario or Scenario("static")
    rc = scenario.replan or ReplanConfig()
    policy = scenario.policy
    rates = [float(r) for r in (rates or [cfg.injection_rate])]
    seeds = [int(s) for s in (seeds or [cfg.seed])]
    points = [(r, s) for r in rates for s in seeds]

    table = bidor_table
    nr_prev = nrank0   # seed plan's fixed point: first replan warm-starts
    if cfg.algo == Algo.BIDOR:
        if table is None:
            plan0 = build_plan_fast(topo, traffic, tracer=tracer)
            table, nr_prev = plan0.table, plan0.nrank
    tables, meta = build_tables(
        topo, traffic, table if cfg.algo == Algo.BIDOR else None,
        cfg.num_vcs)
    batched = make_states(meta, cfg, points)
    q_meta = source_queue_meta(tables, cfg)   # refresh on gen retargets

    # environment state
    base_bw = np.asarray(topo.channel_bw, np.float64)
    bw = base_bw.copy()
    bw_hist = [(0, bw.copy())]   # (cycle, bw) — telemetry normalization
    cur_traffic = np.asarray(traffic, np.float64)
    cur_gen = cur_traffic    # what the sim currently *generates* from
    fault_pending = False
    cur_unroutable = None    # active admission-control mask (shed pairs)

    # the offline matrix rides along as the estimator's cold-start
    # prior (never the ground-truth *current* matrix — that would be
    # the oracle): a fault before any delivery still gets a plan
    estimator = TrafficEstimator(topo.num_nodes, ema=rc.ema,
                                 prior=traffic)
    detector = DriftDetector(threshold=rc.drift_threshold)
    replans: list[Replan] = []

    # boundary grid: control epochs ∪ event cycles ∪ end of run
    total = int(cfg.cycles)
    bounds = set(range(rc.epoch, total, rc.epoch)) | {total}
    bounds |= {int(e.cycle) for e in scenario.events if 0 < e.cycle < total}
    bounds = sorted(bounds)

    nlanes = len(points)
    prev_seq = np.zeros((nlanes,) + (meta["N"],) * 2, np.int64)
    prev_seen = np.zeros((nlanes, meta["C"]), np.int64)
    prev_fwd = np.zeros((nlanes, meta["C"]), np.int64)
    prev_meas = np.zeros(nlanes, np.int64)
    link_peak = np.zeros(nlanes)
    epoch_bounds = []
    sat_th = rc.sat_occupancy if sat_occupancy is None else sat_occupancy
    sat = np.zeros(nlanes, bool)

    # ---- resume from an epoch-boundary snapshot, if one exists ---- #
    resume_i = 0
    snap = checkpoint.load() if checkpoint is not None else None
    if snap is not None:
        arrays, cmeta = snap
        resume_i = int(cmeta["bound_i"])
        batched = maybe_shard_states(
            {k[2:]: jnp.asarray(v) for k, v in arrays.items()
             if k.startswith("s_")})
        sat = np.asarray(arrays["sat"], bool).copy()
        link_peak = np.asarray(arrays["link_peak"], np.float64).copy()
        bw = np.asarray(arrays["bw"], np.float64)
        if "bwh" in arrays and cmeta.get("bwh_cycles"):
            bwh = np.asarray(arrays["bwh"], np.float64)
            bw_hist = [(int(c), bwh[k].copy())
                       for k, c in enumerate(cmeta["bwh_cycles"])]
        else:   # pre-telemetry snapshot: current bw stands in for history
            bw_hist = [(0, bw.copy())]
        cur_traffic = np.asarray(arrays["cur_traffic"], np.float64)
        cur_gen = np.asarray(arrays["cur_gen"], np.float64)
        cur_unroutable = (np.asarray(arrays["cur_unroutable"], bool)
                          if "cur_unroutable" in arrays else None)
        fault_pending = bool(cmeta["fault_pending"])
        estimator._m = (np.asarray(arrays["est_m"], np.float64)
                        if "est_m" in arrays else None)
        detector._ref = (np.asarray(arrays["det_ref"], np.float64)
                         if "det_ref" in arrays else None)
        detector.last_distance = float(cmeta["last_distance"])
        replans = [Replan(**r) for r in cmeta["replans"]]
        if cmeta["has_nr"]:
            nr_prev = NRankResult(
                iterations=int(cmeta["nr_iterations"]),
                **{f: arrays[f"nr_{f}"] for f in _NR_FIELDS})
        # re-point the sim tables at the checkpointed environment (a
        # value-identical hot-swap: retarget is deterministic in its
        # inputs, so unchanged fields rebuild to the same values)
        choice = arrays.get("tab_choice")
        if choice is not None and table is not None:
            # keep the live table in sync so a LATER snapshot (second
            # interruption) records the replanned choice, not the seed's
            table = dataclasses.replace(table, choice=choice)
        tables = retarget_tables(
            tables, topo, traffic=cur_gen,
            choice=(choice if cfg.algo == Algo.BIDOR
                    and choice is not None else None),
            channel_bw=bw)
        q_meta = source_queue_meta(tables, cfg)
        prev_seq = np.asarray(arrays["s_next_seq"], np.int64)
        prev_seen = np.asarray(arrays["s_chan_seen"], np.int64)
        prev_fwd = np.asarray(arrays["s_chan_fwd"], np.int64)
        prev_meas = np.asarray(arrays["s_meas_cnt"], np.int64)
        t_prev = 0
        for j in range(resume_i):
            epoch_bounds.append((t_prev, bounds[j]))
            t_prev = bounds[j]

    t0 = bounds[resume_i - 1] if resume_i else 0
    attempt = len(replans)   # replan ordinal in the session (trace args)
    for bound_i in range(resume_i, len(bounds)):
        t1 = bounds[bound_i]
        if checkpoint is not None and bound_i > resume_i:
            checkpoint.save(*_ctrl_snapshot(
                batched, bound_i=bound_i, sat=sat, link_peak=link_peak,
                bw=bw, cur_traffic=cur_traffic, cur_gen=cur_gen,
                cur_unroutable=cur_unroutable,
                fault_pending=fault_pending, estimator=estimator,
                detector=detector, replans=replans, table=table,
                nr_prev=nr_prev, bw_hist=bw_hist))
        with span(tracer, "epoch", cat="sim", t0=t0, t1=t1,
                  cycles=t1 - t0, scenario=scenario.name,
                  policy=policy) as a:
            builds = runner_builds()
            runner = get_runner(meta, cfg, t1 - t0, num_lanes=nlanes,
                                multi_device=multi_device)
            a["compiled"] = runner_builds() > builds
            batched = runner(tables, batched)
            if tracer.enabled:
                # block so the span times the device work, not the dispatch
                jax.block_until_ready(batched)
        epoch_bounds.append((t0, t1))
        t0 = t1

        with span(tracer, "boundary", cat="ctrl", cycle=t1) as a:
            # ---- read counters (one small host transfer) ---- #
            seq, seen, fwd, meas = (
                np.asarray(jax.device_get(batched[k]), np.int64)
                for k in _COUNTERS)
            host_bytes = sum(batched[k].nbytes for k in _COUNTERS)
            d_seq, d_seen = seq - prev_seq, seen - prev_seen
            d_fwd, d_meas = fwd - prev_fwd, meas - prev_meas
            prev_seq, prev_seen, prev_fwd, prev_meas = seq, seen, fwd, meas

            # time-resolved max normalized link load (this epoch's bw)
            live = bw > 0
            for i in range(nlanes):
                if d_meas[i] > 0 and live.any():
                    loads = d_fwd[i, live] / float(d_meas[i]) / bw[live]
                    link_peak[i] = max(link_peak[i], float(loads.max()))

            if t1 > cfg.warmup:
                # saturation accumulates from post-warmup reads only — a
                # transient warmup spike must not permanently latch a lane
                sat |= queue_occupancy(tables, cfg, batched["q_size"],
                                       q_meta) >= sat_th
                host_bytes += batched["q_size"].nbytes

            estimator.update(d_seq.sum(axis=0))
            drifted = detector.update(d_seen.sum(axis=0))
            if tracer.enabled:
                tracer.counter("drift_tv", {"tv": detector.last_distance},
                               cat="ctrl")
                if drifted:
                    tracer.instant(
                        "drift_detected", cat="ctrl",
                        args={"cycle": t1, "tv": detector.last_distance})
                est = estimator.matrix
                a["nonzero_pairs"] = (0 if est is None
                                      else int(np.count_nonzero(est)))

            if t1 >= total:
                a["host_bytes"] = host_bytes
                break

            # ---- apply due events (the environment) ---- #
            due = [e for e in scenario.events if e.cycle == t1]
            event_kinds: set = set()
            if due:
                bw, new_traffic, rate_scale, event_kinds = _apply_events(
                    due, bw, topo, base_bw)
                if tracer.enabled:
                    for ev in due:
                        ea = {"cycle": t1}
                        if isinstance(ev, LinkFail):
                            ea["bw_scale"] = ev.bw_scale
                        tracer.instant(type(ev).__name__, cat="env",
                                       args=ea)
                if "fault" in event_kinds:
                    bw_hist.append((t1, bw.copy()))
                gen_traffic = new_traffic
                if new_traffic is not None and cur_unroutable is not None:
                    # an active shed outlives a traffic epoch: the dead
                    # link is still dead, so the new matrix generates
                    # under the same admission-control mask until the
                    # next replan
                    gen_traffic = np.where(cur_unroutable, 0.0, new_traffic)
                tables = retarget_tables(
                    tables, topo,
                    traffic=gen_traffic,
                    channel_bw=bw if "fault" in event_kinds else None)
                if gen_traffic is not None:
                    cur_gen = gen_traffic
                    q_meta = source_queue_meta(tables, cfg)
                    host_bytes += tables.p_gen.nbytes
                if new_traffic is not None:
                    cur_traffic = new_traffic
                if rate_scale is not None:
                    # absolute vs base: rate_scale=1.0 restores the
                    # original injection rates after a previously scaled
                    # epoch
                    batched["rate"] = jnp.asarray(
                        [r * rate_scale for r, _ in points], jnp.float32)
                fault_pending |= "fault" in event_kinds
            a["host_bytes"] = host_bytes

            # ---- control decision ---- #
            do = False
            if cfg.algo == Algo.BIDOR and policy == "oracle":
                do, trigger, m = bool(due), "event", cur_traffic
            elif cfg.algo == Algo.BIDOR and policy == "online":
                # faults are signalled out of band (hardware link state,
                # as in real fabrics); traffic drift must be *detected*
                trigger = "fault" if fault_pending else "drift"
                # estimator.matrix backs off to the offline prior until
                # the first packets arrive, so a cold-start fault replans
                # from the plan-time statistics; None only when there is
                # no demand to plan for at all
                m = estimator.matrix
                do = (fault_pending or drifted) and m is not None
        if not do:
            continue
        drift_dist = detector.last_distance
        rt = tagged(tracer, replan=attempt)
        attempt += 1
        with span(rt, "replan", cat="ctrl", cycle=t1,
                  trigger=trigger) as ra:
            table, nr_prev = replan(
                topo, m, bw, nr_prev,
                warm=rc.warm, greedy_sweeps=rc.greedy_sweeps, tracer=rt)
            with span(rt, "hot_swap", cat="ctrl", cycle=t1) as ha:
                # hot-swap guard: a replan that sheds most of the demanded
                # pairs would silently wedge the run behind a near-empty
                # table — keep the previous (still-certified) table and
                # record the rejection
                shed, n_dem = 0, 0
                if table.unroutable is not None:
                    demanded = np.asarray(cur_traffic) > 0
                    n_dem = int(demanded.sum())
                    shed = int((table.unroutable & demanded).sum())
                shed_frac = shed / n_dem if n_dem else 0.0
                rejected = shed_frac > rc.max_shed
                ha.update(shed_pairs=shed, rejected=rejected)
                if not rejected:
                    # admission control: shed unroutable pairs from
                    # generation; when the new plan can serve everything
                    # (e.g. after LinkRecover), restore the full current
                    # matrix — a previous shed must not outlive the fault
                    # that caused it
                    gen = cur_traffic
                    cur_unroutable = None
                    if (table.unroutable is not None
                            and table.unroutable.any()):
                        cur_unroutable = table.unroutable
                        gen = np.where(cur_unroutable, 0.0, cur_traffic)
                    tables = retarget_tables(tables, topo,
                                             choice=table.choice,
                                             traffic=gen)
                    cur_gen = gen
                    q_meta = source_queue_meta(tables, cfg)
            detector.reset()
            fault_pending = False
            if rejected:
                ra.drop()
                rt.instant(
                    "hot_swap_rejected", cat="ctrl",
                    args={"cycle": t1, "trigger": trigger,
                          "shed_frac": round(shed_frac, 4),
                          "max_shed": rc.max_shed})
                log.event("replan_rejected",
                          f"ctrl[{scenario.name}/{policy}] hot-swap "
                          f"rejected @ {t1}: shed {shed_frac:.0%} > "
                          f"max {rc.max_shed:.0%}", cycle=t1,
                          trigger=trigger)
            else:
                replans.append(Replan(
                    cycle=t1, trigger=trigger,
                    iterations=nr_prev.iterations,
                    unroutable_pairs=int(table.unroutable.sum())
                    if table.unroutable is not None else 0,
                    drift_distance=drift_dist))
                ra.update(warm=rc.warm and nr_prev is not None,
                          iterations=int(nr_prev.iterations),
                          unroutable=replans[-1].unroutable_pairs,
                          drift_tv=drift_dist)
        if rejected:
            continue
        log.event("replan",
                  f"ctrl[{scenario.name}/{policy}] replan @ {t1} "
                  f"({trigger}), {nr_prev.iterations} iters",
                  cycle=t1, trigger=trigger)

    results = []
    host = jax.device_get(batched)
    for i, (rate, seed) in enumerate(points):
        o = jax.tree.map(lambda x: x[i], host)
        results.append(postprocess(o, cfg, topo, rate=rate, seed=seed,
                                   saturated=bool(sat[i])))
    telemetry = Telemetry.from_state(host, cfg)
    if telemetry is not None:
        telemetry = telemetry.with_bw(_bw_slots(
            bw_hist, resolved_epoch(cfg), cfg.tel_slots, total))
    watchdog = WatchdogReport.from_state(host, cfg)
    if watchdog is not None and watchdog.tripped and tracer.enabled:
        tracer.instant("watchdog_tripped", cat="ctrl",
                       args=watchdog.trace_args())
    return ControlledResult(
        scenario=scenario.name, policy=policy, points=points,
        results=results, replans=replans, link_peak=link_peak,
        epoch_bounds=epoch_bounds, telemetry=telemetry,
        watchdog=watchdog)
