"""Batched simulation-campaign engine.

Every headline number in the paper (42.9% throughput, 86.4%/95.3% latency)
comes from sweeping (algorithm × traffic pattern × injection rate × seed)
through the flit simulator.  This module turns that sweep into a first-class
subsystem:

* A declarative :class:`CampaignSpec` names the grid once.
* All (rate, seed) points of a cell — one (algorithm, pattern) pair — run
  inside a SINGLE jitted, vmapped call: per-run state is a pytree batched
  over a leading axis (``repro.noc.sim.make_states``), static lookup tables
  are traced arguments shared by every lane.  One XLA compilation per
  (mesh, algorithm, flow-control, chunk-length) tuple covers the whole
  campaign.
* Explicit warmup → measure → drain phasing (``SimConfig.warmup`` /
  ``.drain``): statistics only inside the measurement window, injection
  halted for the trailing drain cycles so in-flight packets land and
  latency tails are complete.
* Saturation early-exit: the cell advances in ``chunk``-cycle slices; after
  each slice a cheap host-side detector reads source-queue occupancy, and
  once EVERY lane is saturated (queues ≥ ``sat_occupancy`` of capacity) the
  remaining cycles are skipped — per-lane ``meas_cnt`` keeps the statistics
  exactly normalized.  ``chunk=0`` disables chunking (one call per cell).

:class:`CampaignResult` returns per-point latency percentiles (p50/p90/p99
from in-simulator histograms), throughput, max link load, and per-cell
wall-clock, with grid accessors for plotting/tables.
"""

from __future__ import annotations

import dataclasses
import re
import time
from typing import Sequence

import jax
import numpy as np

from repro.core import traffic as traffic_mod
from repro.core.plan_fast import build_plans_batched
from repro.core.topology import Topology
from repro.obs.log import EventLog
from repro.obs.probe import Telemetry
from repro.obs.trace import NULL_TRACER, span, tagged
from .sim import (build_tables, get_runner, make_states, postprocess,
                  queue_occupancy, runner_builds, source_queue_meta,
                  static_bw_slots)
from .simconfig import Algo, SimConfig, SimResult

__all__ = ["CampaignSpec", "CampaignPoint", "CampaignResult",
           "run_campaign", "CellKey", "CellOutcome", "campaign_cells",
           "CampaignExecutor", "csv_rows"]


@dataclasses.dataclass(frozen=True)
class CampaignSpec:
    """Declarative grid of simulations.

    Attributes:
      topo: the network under test.
      topos: optional *topology axis* — when non-empty, the whole grid runs
        once per listed topology (``topo`` is ignored); string patterns are
        re-resolved per topology, and BiDOR plans (including fault masking
        for topologies with dead channels) are rebuilt per topology.
      algos: routing algorithms to sweep.
      patterns: traffic patterns — names resolved through
        ``repro.core.traffic.PATTERNS`` or explicit ``(name, matrix)``
        pairs.
      rates: injection rates (flits/cycle/I/O-port).
      seeds: RNG seeds; each (rate, seed) is an independent lane of the
        vmapped batch.
      base: simulation parameters shared by every point (``algo``,
        ``injection_rate`` and ``seed`` fields are overridden per point).
      chunk: host-loop granularity in cycles for the saturation early-exit;
        0 runs each cell as one jitted call of ``base.cycles`` cycles.
      sat_occupancy: source-queue occupancy fraction above which a lane is
        declared saturated.
      scenarios: optional fault/drift dynamics axis —
        :class:`repro.noc.ctrl.Scenario` entries.  Empty () keeps the
        classic static grid; with scenarios, every (algo, pattern,
        scenario) cell runs through the control plane's event-driven loop
        (:func:`repro.noc.ctrl.run_controlled`), (rate, seed) points still
        batched as lanes of one vmapped state.
      multi_device: ``shard_map`` lane parallelism — ``True`` forces the
        explicit multi-device runner (lanes split over all local devices,
        carry buffers donated), ``False`` pins single-device execution,
        ``None`` (default) auto-enables whenever >1 device is visible and
        the (rate, seed) lane count divides evenly.  Results are
        bit-identical either way (``tests/test_multidevice.py``); on CPU
        expose cores with
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
      workloads: ML-workload axis — entries are
        :class:`repro.noc.mltraffic.MLWorkload` instances (anything with
        ``.name`` and ``.matrix_for(topo)``) or explicit ``(name,
        matrix)`` pairs.  Workloads join the pattern axis as extra items
        (same plan building, plan cache, certifier gate, and cell
        enumeration), tagged with their name in the ``workload`` CSV /
        telemetry column so derived and synthetic rows stay separable.
    """

    topo: Topology | None
    algos: tuple[Algo, ...]
    patterns: tuple
    rates: tuple[float, ...]
    seeds: tuple[int, ...] = (0,)
    base: SimConfig = SimConfig()
    chunk: int = 0
    sat_occupancy: float = 0.9
    scenarios: tuple = ()
    topos: tuple[Topology, ...] = ()
    multi_device: bool | None = None
    workloads: tuple = ()

    def __post_init__(self):
        if not (self.algos and (self.patterns or self.workloads)
                and self.rates and self.seeds):
            raise ValueError("campaign grid must be non-empty on all axes")
        if self.topo is None and not self.topos:
            raise ValueError("provide topo or a non-empty topos axis")

    @property
    def topo_axis(self) -> tuple[Topology, ...]:
        return self.topos or (self.topo,)

    @property
    def num_points(self) -> int:
        return (len(self.algos)
                * (len(self.patterns) + len(self.workloads))
                * len(self.rates)
                * len(self.seeds) * max(len(self.scenarios), 1)
                * len(self.topo_axis))

    def pattern_items(self, topo: Topology | None = None,
                      ) -> list[tuple[str, np.ndarray]]:
        """Resolve the combined pattern ⊕ workload axis to (name,
        traffic matrix) pairs — workload items come last, in axis
        order (``campaign_cells`` relies on this item indexing)."""
        topo = self.topo if topo is None else topo
        items = []
        for p in self.patterns:
            if isinstance(p, str):
                if p not in traffic_mod.PATTERNS:
                    raise KeyError(
                        f"unknown traffic pattern {p!r}; available: "
                        f"{sorted(traffic_mod.PATTERNS)}")
                items.append((p, traffic_mod.PATTERNS[p](topo)))
            else:
                name, tm = p
                items.append((str(name), np.asarray(tm, np.float64)))
        for w in self.workloads:
            if hasattr(w, "matrix_for"):
                items.append((str(w.name), w.matrix_for(topo)))
            else:
                name, tm = w
                items.append((str(name), traffic_mod.from_pair_counts(
                    topo, np.asarray(tm, np.float64))))
        return items


@dataclasses.dataclass(frozen=True)
class CampaignPoint:
    """One grid point: the cell coordinates plus its SimResult."""

    algo: Algo
    pattern: str
    rate: float
    seed: int
    result: SimResult
    scenario: str = "static"
    topo: str = ""
    # name of the originating CampaignSpec.workloads entry; "" for
    # synthetic patterns (the workload's name doubles as its pattern)
    workload: str = ""


@dataclasses.dataclass
class CampaignResult:
    """Structured campaign output.

    ``points`` is ordered (topo, pattern, algo, scenario, rate, seed)
    nested-loop major.  ``wall_clock_s`` maps one key per cell to the
    wall-clock of its single batched call chain (compile time included on
    first use).  The key shape follows the active axes:

    * ``(algo name, pattern)`` — classic single-topology static grid;
    * ``(algo name, pattern, scenario)`` — with a ``scenarios`` axis;
    * ``(topo, algo name, pattern)`` /
      ``(topo, algo name, pattern, scenario)`` — with a ``topos`` axis
      (the topology name is *prepended*).

    :meth:`summary` labels each part explicitly, so logs stay readable
    whatever the key arity.
    """

    spec: CampaignSpec
    points: list[CampaignPoint]
    wall_clock_s: dict[tuple[str, ...], float]
    total_wall_clock_s: float

    def select(self, algo: Algo | None = None, pattern: str | None = None,
               rate: float | None = None,
               seed: int | None = None,
               scenario: str | None = None,
               topo: str | None = None,
               workload: str | None = None) -> list[CampaignPoint]:
        out = []
        for p in self.points:
            if algo is not None and p.algo != algo:
                continue
            if pattern is not None and p.pattern != pattern:
                continue
            if rate is not None and p.rate != rate:
                continue
            if seed is not None and p.seed != seed:
                continue
            if scenario is not None and p.scenario != scenario:
                continue
            if topo is not None and p.topo != topo:
                continue
            if workload is not None and p.workload != workload:
                continue
            out.append(p)
        return out

    def _resolve_axis(self, name: str, value: str | None,
                      options: tuple[str, ...]) -> str:
        """Default a cell axis for single-valued campaigns; on a
        multi-valued axis an explicit value is REQUIRED — silently
        pooling points across scenarios/topologies is exactly the
        last-write-wins corruption this guard exists to prevent."""
        if value is not None:
            if value not in options:
                raise KeyError(f"unknown {name} {value!r}; campaign has "
                               f"{list(options)}")
            return value
        if len(options) == 1:
            return options[0]
        raise ValueError(
            f"ambiguous {name} axis: this campaign has "
            f"{list(options)}; pass {name}=... to the accessor")

    @property
    def scenario_names(self) -> tuple[str, ...]:
        return tuple(s.name for s in self.spec.scenarios) or ("static",)

    @property
    def topo_names(self) -> tuple[str, ...]:
        return tuple(t.name for t in self.spec.topo_axis)

    def grid(self, field: str, algo: Algo, pattern: str,
             scenario: str | None = None,
             topo: str | None = None) -> np.ndarray:
        """(num_rates, num_seeds) array of a SimResult field for ONE cell.

        ``scenario`` / ``topo`` select along the scenario and topology
        axes; they default only when the campaign has a single value on
        that axis, and raise otherwise (an ambiguous selection would
        overlay every scenario/topology into one grid, last write wins).
        """
        scenario = self._resolve_axis("scenario", scenario,
                                      self.scenario_names)
        topo = self._resolve_axis("topo", topo, self.topo_names)
        rates, seeds = list(self.spec.rates), list(self.spec.seeds)
        g = np.zeros((len(rates), len(seeds)))
        filled = np.zeros((len(rates), len(seeds)), bool)
        for p in self.select(algo=algo, pattern=pattern,
                             scenario=scenario, topo=topo):
            ij = rates.index(p.rate), seeds.index(p.seed)
            if filled[ij]:
                raise ValueError(
                    f"duplicate point for (rate={p.rate}, seed={p.seed}) "
                    f"in cell ({algo.name}, {pattern!r}, {scenario!r}, "
                    f"{topo!r}) — pattern names are not unique in this "
                    f"campaign; use explicit (name, matrix) labels")
            filled[ij] = True
            g[ij] = getattr(p.result, field)
        if not filled.all():
            raise ValueError(
                f"cell ({algo.name}, {pattern!r}, {scenario!r}, {topo!r}) "
                f"is missing {int((~filled).sum())} of the "
                f"{filled.size} (rate, seed) points")
        return g

    def mean_over_seeds(self, field: str, algo: Algo, pattern: str,
                        scenario: str | None = None,
                        topo: str | None = None) -> np.ndarray:
        return self.grid(field, algo, pattern, scenario=scenario,
                         topo=topo).mean(axis=1)

    def saturation_throughput(self, algo: Algo, pattern: str,
                              scenario: str | None = None,
                              topo: str | None = None) -> float:
        """Max seed-averaged accepted throughput across the rate sweep."""
        return float(self.mean_over_seeds(
            "throughput", algo, pattern, scenario=scenario,
            topo=topo).max())

    CSV_HEADER = ["topo", "scenario", "pattern", "workload", "algo",
                  "rate", "seed",
                  "throughput",
                  "offered", "avg_lat", "p50_lat", "p90_lat", "p99_lat",
                  "max_lat", "lcv", "link_load_max", "reorder",
                  "saturated", "meas_cycles"]

    def to_rows(self) -> list[list]:
        return csv_rows(self.points)

    def _wall_key_labels(self, key: tuple[str, ...]) -> list[str]:
        """Name the parts of one ``wall_clock_s`` key (see the class
        docstring for the shape rules)."""
        parts = list(key)
        labels = []
        if len(self.spec.topo_axis) > 1:
            labels.append("topo")
        labels += ["algo", "pattern"]
        if self.spec.scenarios:
            labels.append("scenario")
        if len(labels) != len(parts):   # foreign/legacy key: best effort
            return [str(p) for p in parts]
        return [f"{l}={p}" for l, p in zip(labels, parts)]

    def summary(self) -> str:
        lines = [f"campaign: {self.spec.num_points} points in "
                 f"{self.total_wall_clock_s:.1f}s wall-clock"]
        for key, dt in self.wall_clock_s.items():
            cell = " ".join(f"{part:14s}"
                            for part in self._wall_key_labels(key))
            lines.append(f"  cell {cell} {dt:6.2f}s")
        return "\n".join(lines)


def csv_rows(points: Sequence[CampaignPoint]) -> list[list]:
    """CSV rows (matching ``CampaignResult.CSV_HEADER``) for a point list.

    Module-level so the campaign service can stream a cell's rows the
    moment the cell completes, with byte-identical formatting to a full
    ``CampaignResult.to_rows`` dump.
    """
    rows = []
    for p in points:
        r = p.result
        rows.append([p.topo, p.scenario, p.pattern, p.workload,
                     p.algo.name,
                     p.rate, p.seed,
                     f"{r.throughput:.4f}", f"{r.offered:.4f}",
                     f"{r.avg_latency:.1f}", f"{r.p50_latency:.1f}",
                     f"{r.p90_latency:.1f}", f"{r.p99_latency:.1f}",
                     f"{r.max_latency:.0f}", f"{r.lcv:.3f}",
                     f"{r.link_load_max:.4f}", r.reorder_value,
                     int(r.saturated), r.meas_cycles])
    return rows


def _run_cell(spec: CampaignSpec, cfg: SimConfig, tables, meta,
              points: list[tuple[float, int]], tracer=NULL_TRACER):
    """Advance one (algo, pattern) cell; returns (host state, sat flags).

    The cell is one vmapped batch over ``points``.  With ``spec.chunk``
    set, execution proceeds in chunk-cycle slices so the host can stop the
    whole batch as soon as every lane is saturated.  Each runner call is
    a ``chunk`` span of ``tracer``.
    """
    batched = make_states(meta, cfg, points)
    total = int(cfg.cycles)
    chunk = int(spec.chunk) or total
    sat = np.zeros(len(points), bool)
    q_meta = source_queue_meta(tables, cfg)   # static for the whole cell
    done = 0
    while done < total:
        step_cycles = min(chunk, total - done)
        with span(tracer, "chunk", cat="sim", cycles=step_cycles) as a:
            builds = runner_builds()
            runner = get_runner(meta, cfg, step_cycles,
                                num_lanes=len(points),
                                multi_device=spec.multi_device)
            a["compiled"] = runner_builds() > builds
            batched = runner(tables, batched)
        done += step_cycles
        if done > cfg.warmup:
            # saturation accumulates from post-warmup reads only — a
            # transient warmup spike must not permanently latch a lane
            occ = queue_occupancy(tables, cfg, batched["q_size"], q_meta)
            sat |= occ >= spec.sat_occupancy
            if done < total and sat.all():
                break  # every lane saturated: verdict reached
    return jax.device_get(batched), sat


# --------------------------------------------------------------------- #
# resumable cell machinery (the campaign service's unit of work)
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class CellKey:
    """Coordinates of one campaign cell in the spec's enumeration order.

    ``index`` is the cell's position in :func:`campaign_cells` order —
    the canonical topo → pattern item → algo → scenario nesting — which
    is also the order of ``CampaignResult.points`` (lane-major within a
    cell).  ``item_i`` carries the *pattern item index*, not just the
    name: explicit ``(name, matrix)`` patterns may repeat a name with
    different matrices.  ``scen_i`` is -1 for the static (no-scenario)
    cell.
    """

    index: int
    topo_i: int
    topo: str
    item_i: int
    pattern: str
    algo: Algo
    scen_i: int
    scenario: str
    # the workload-axis name when this cell's item is a workload
    # (item_i >= len(spec.patterns)); "" for synthetic pattern cells
    workload: str = ""

    @property
    def slug(self) -> str:
        """Filesystem-safe unique cell name (checkpoint file stem)."""
        parts = (self.topo, f"i{self.item_i}", self.pattern,
                 self.algo.name, self.scenario)
        clean = "_".join(re.sub(r"[^A-Za-z0-9.+-]+", "-", p)
                         for p in parts)
        return f"cell{self.index:04d}_{clean}"

    def wall_key(self, spec: CampaignSpec) -> tuple[str, ...]:
        """The cell's ``CampaignResult.wall_clock_s`` key."""
        key: tuple[str, ...] = (self.algo.name, self.pattern)
        if self.scen_i >= 0:
            key = key + (self.scenario,)
        if len(spec.topo_axis) > 1:
            key = (self.topo,) + key
        return key


@dataclasses.dataclass
class CellOutcome:
    """One executed cell: its per-lane results plus wall-clock."""

    key: CellKey
    results: list[SimResult]    # one per (rate, seed) lane, rate-major
    wall_s: float
    # per-lane probe rings when cfg.telemetry is on (None otherwise);
    # bw-normalized — static cells against the topology's bandwidths,
    # scenario cells against the per-slot fault-tracking timeline
    telemetry: "Telemetry | None" = None


def _pattern_names(spec: CampaignSpec) -> list[str]:
    """Combined pattern ⊕ workload axis names without resolving matrices
    (cheap enumeration; workload names come last, matching
    ``CampaignSpec.pattern_items`` item order)."""
    names = [p if isinstance(p, str) else str(p[0]) for p in spec.patterns]
    names += [str(w.name) if hasattr(w, "matrix_for") else str(w[0])
              for w in spec.workloads]
    return names


def campaign_cells(spec: CampaignSpec) -> list[CellKey]:
    """Enumerate the spec's cells in canonical execution order.

    The nesting (topo → pattern item → algo → scenario) matches the
    historical ``run_campaign`` loop exactly, so ``CampaignResult.points``
    built from this order is identical to a pre-service campaign's.
    """
    names = _pattern_names(spec)
    n_pat = len(spec.patterns)
    cells: list[CellKey] = []
    index = 0
    for topo_i, topo in enumerate(spec.topo_axis):
        for item_i, pat_name in enumerate(names):
            for algo in spec.algos:
                for scen_i, scen in enumerate(spec.scenarios or (None,)):
                    cells.append(CellKey(
                        index=index, topo_i=topo_i, topo=topo.name,
                        item_i=item_i, pattern=pat_name, algo=algo,
                        scen_i=-1 if scen is None else scen_i,
                        scenario="static" if scen is None else scen.name,
                        workload=pat_name if item_i >= n_pat else ""))
                    index += 1
    return cells


@dataclasses.dataclass
class _ItemPrep:
    """Per-(topology, pattern item) execution inputs."""

    name: str
    tm: np.ndarray
    table: object | None       # BiDORTable (None when BiDOR absent)
    nrank: object | None       # warm-start fixed point for replans
    bidor_tm: np.ndarray       # admission-controlled generation matrix


class CampaignExecutor:
    """Executes campaign cells one at a time, in any order.

    Holds everything a cell run needs — the resolved pattern matrices,
    BiDOR plans (admission-controlled for degraded topologies), and the
    lane list — prepared lazily per topology so resuming a job at cell k
    does not re-plan topologies whose cells are all complete.

    ``plan_cache`` (a :class:`repro.core.plan_cache.PlanCache`) serves
    plan builds by content key; when every pattern of a topology hits,
    ``build_plans_batched`` is not called at all for that topology.
    """

    def __init__(self, spec: CampaignSpec, *,
                 bidor_tables: dict[str, np.ndarray] | None = None,
                 plan_cache=None, verbose: bool = False, tracer=None):
        self.spec = spec
        self.bidor_tables = bidor_tables
        self.plan_cache = plan_cache
        self.verbose = verbose
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.log = EventLog(verbose=verbose)
        self.points = [(float(r), int(s))
                       for r in spec.rates for s in spec.seeds]
        self._prepped: dict[int, list[_ItemPrep]] = {}
        self._plan_builds = 0   # build_plans_batched calls (trace args)

    # ------------------------------------------------------------- #
    def _build_plans(self, topo: Topology, items, need: list[int]):
        """Plans for the needed pattern items, through the cache when
        one is configured (misses batched into one device call)."""
        plans: dict[int, object] = {}
        if not need:
            return plans
        down = topo.down_channels
        dc = down if down.size else None
        cache = self.plan_cache
        if cache is None:
            self._plan_builds += 1
            built = build_plans_batched(topo, [items[i][1] for i in need],
                                        down_channels=dc,
                                        tracer=self.tracer)
            return dict(zip(need, built))
        from repro.core.plan_fast import gate_plan, plan_cache_key
        miss: list[tuple[int, str]] = []
        for i in need:
            key = plan_cache_key(topo, items[i][1], down_channels=dc)
            hit = cache.get(key, topo)
            if hit is not None:
                # cache admission: a stored clean certificate satisfies
                # the deadlock gate; anything else re-certifies
                cert = cache.get_cert(key)
                if cert is not None and cert.verdict == "clean":
                    hit = dataclasses.replace(hit, cert=cert)
                else:
                    hit = gate_plan(topo, hit, tracer=self.tracer,
                                    label=f"cache_admission:{topo.name}")
                plans[i] = hit
                if self.tracer.enabled:
                    self.tracer.instant(
                        "plan_cache_hit", cat="plan",
                        args={"item": i, "topo": topo.name, "store": True})
            else:
                miss.append((i, key))
        if miss:
            self._plan_builds += 1
            built = build_plans_batched(
                topo, [items[i][1] for i, _ in miss], down_channels=dc,
                tracer=self.tracer)
            for (i, key), plan in zip(miss, built):
                plans[i] = plan
                cache.put(key, plan)
            cache.stats.device_builds += 1
        return plans

    def _prep_topo(self, topo_i: int) -> list[_ItemPrep]:
        if topo_i in self._prepped:
            return self._prepped[topo_i]
        spec = self.spec
        bidor_tables = self.bidor_tables
        topo = spec.topo_axis[topo_i]
        items = spec.pattern_items(topo)
        # one vmapped device call plans every pattern that needs one (the
        # campaign's pattern axis; scenario replans reuse these as their
        # warm-start seeds).  Keyed by item index: explicit (name, matrix)
        # patterns may repeat a name with different matrices.
        plans: dict[int, object] = {}
        if Algo.BIDOR in spec.algos:
            need = [i for i, (name, _) in enumerate(items)
                    if not (bidor_tables and name in bidor_tables)
                    or spec.scenarios]
            plans = self._build_plans(topo, items, need)
        prepped: list[_ItemPrep] = []
        for item_i, (pat_name, tm) in enumerate(items):
            pat_table = None
            pat_nrank = None  # seed fixed point: scenario replans warm-start
            if Algo.BIDOR in spec.algos:
                if bidor_tables and pat_name in bidor_tables:
                    choice = np.asarray(bidor_tables[pat_name], np.int8)
                    if spec.scenarios:  # scenario cells need the full plan
                        pat_table = dataclasses.replace(
                            plans[item_i].table, choice=choice)
                        pat_nrank = plans[item_i].nrank
                    else:
                        from repro.core.bidor import dor_table
                        pat_table = dataclasses.replace(
                            dor_table(topo), choice=choice)
                else:
                    pat_table = plans[item_i].table
                    pat_nrank = plans[item_i].nrank
            # admission control: pairs no dimension order can serve on a
            # degraded topology are shed from BiDOR's generation matrix
            # (the control plane does the same after a replan)
            bidor_tm = tm
            if (pat_table is not None and pat_table.unroutable is not None
                    and pat_table.unroutable.any()):
                bidor_tm = np.where(pat_table.unroutable, 0.0, tm)
            prepped.append(_ItemPrep(name=pat_name, tm=tm, table=pat_table,
                                     nrank=pat_nrank, bidor_tm=bidor_tm))
        self._prepped[topo_i] = prepped
        return prepped

    # ------------------------------------------------------------- #
    def run_cell(self, key: CellKey, *, checkpoint=None) -> CellOutcome:
        """Execute one cell (all its (rate, seed) lanes, one batch).

        ``checkpoint`` — optional epoch-boundary checkpointer handed to
        the control plane for scenario cells (see
        ``repro.noc.ctrl.run_controlled``); static cells run in one
        chunked call and checkpoint only at completion.
        """
        spec = self.spec
        tracer = tagged(self.tracer, slug=key.slug)
        topo = spec.topo_axis[key.topo_i]
        with span(tracer, "prep_topo", cat="campaign") as a:
            builds = self._plan_builds
            prep = self._prep_topo(key.topo_i)[key.item_i]
            a["cached"] = self._plan_builds == builds
        algo = key.algo
        cfg = spec.base.replace(algo=algo)
        scen = spec.scenarios[key.scen_i] if key.scen_i >= 0 else None
        with span(tracer, "cell", cat="campaign", topo=key.topo,
                  pattern=key.pattern, algo=algo.name,
                  scenario=key.scenario, lanes=len(self.points)):
            t0 = time.perf_counter()
            cell_tm = prep.bidor_tm if algo == Algo.BIDOR else prep.tm
            telemetry = None
            if scen is None:
                tables, meta = build_tables(
                    topo, cell_tm,
                    prep.table if algo == Algo.BIDOR else None, cfg.num_vcs)
                host, sat = _run_cell(spec, cfg, tables, meta, self.points,
                                      tracer)
                results = []
                for i, (rate, seed) in enumerate(self.points):
                    o = jax.tree.map(lambda x: x[i], host)
                    results.append(postprocess(
                        o, cfg, topo, rate=rate, seed=seed,
                        saturated=bool(sat[i])))
                telemetry = Telemetry.from_state(host, cfg)
                if telemetry is not None:
                    telemetry = telemetry.with_bw(static_bw_slots(topo, cfg))
            else:
                from .ctrl import run_controlled
                ctrl_res = run_controlled(
                    topo, cell_tm, cfg, scen,
                    rates=[float(r) for r in spec.rates],
                    seeds=list(spec.seeds),
                    bidor_table=prep.table if algo == Algo.BIDOR else None,
                    nrank0=prep.nrank if algo == Algo.BIDOR else None,
                    sat_occupancy=spec.sat_occupancy,
                    multi_device=spec.multi_device,
                    checkpoint=checkpoint,
                    verbose=self.verbose,
                    tracer=tracer)
                results = [ctrl_res.result_with_peak(i)
                           for i in range(len(self.points))]
                telemetry = ctrl_res.telemetry
            dt = time.perf_counter() - t0
        self.log.event("cell_done",
                       f"campaign cell {key.topo:16s} {key.pattern:12s} "
                       f"{algo.name:8s} {key.scenario:12s} "
                       f"{len(self.points)} pts in {dt:.2f}s",
                       cell=key.slug, wall_s=round(dt, 3))
        return CellOutcome(key=key, results=results, wall_s=dt,
                           telemetry=telemetry)

    def cell_points(self, outcome: CellOutcome) -> list[CampaignPoint]:
        """The cell's CampaignPoints, in canonical lane order."""
        k = outcome.key
        return [CampaignPoint(algo=k.algo, pattern=k.pattern, rate=rate,
                              seed=seed, result=res, scenario=k.scenario,
                              topo=k.topo, workload=k.workload)
                for (rate, seed), res in zip(self.points, outcome.results)]


def run_campaign(spec: CampaignSpec, *,
                 bidor_tables: dict[str, np.ndarray] | None = None,
                 plan_cache=None,
                 verbose: bool = False,
                 tracer=None) -> CampaignResult:
    """Execute the full campaign grid.

    BiDOR plans are built per pattern from that pattern's own matrix (the
    paper's offline-statistics assumption); pass ``bidor_tables`` (pattern
    name → (N, N) choice table) to override, e.g. with aggregate-trace
    plans.  ``plan_cache`` serves/stores those builds by content key (see
    :class:`repro.core.plan_cache.PlanCache`).

    With ``spec.scenarios`` set, each (algo, pattern, scenario) cell runs
    the control plane's event-driven loop instead of the static cell —
    the scenario's events (link failures, drift epochs) apply mid-run and
    its policy decides when plans hot-swap.  ``SimResult.link_load_max``
    then reports the *time-resolved* peak (max over control epochs of the
    max bandwidth-normalized link load), since a mid-run failure changes
    the normalization.

    This is the blocking, in-memory driver over the resumable cell
    machinery; ``repro.noc.service`` runs the same cells as a
    checkpointed job.
    """
    t_start = time.perf_counter()
    executor = CampaignExecutor(spec, bidor_tables=bidor_tables,
                                plan_cache=plan_cache, verbose=verbose,
                                tracer=tracer)
    out_points: list[CampaignPoint] = []
    wall: dict[tuple, float] = {}
    for key in campaign_cells(spec):
        outcome = executor.run_cell(key)
        wall[key.wall_key(spec)] = outcome.wall_s
        out_points.extend(executor.cell_points(outcome))
    return CampaignResult(spec=spec, points=out_points, wall_clock_s=wall,
                          total_wall_clock_s=time.perf_counter() - t_start)

