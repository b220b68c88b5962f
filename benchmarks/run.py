"""Benchmark orchestrator — one entry per paper table/figure + the
beyond-paper ICI analyses.

  fig1      paper Fig. 1  — load distribution vs N-Rank prediction
  table1    paper Table 1 — LCV per algorithm × scenario
  fig8      paper Fig. 8  — throughput/latency/reorder vs injection rate
  fig9      paper Fig. 9  — realistic Clos-leaf workload
  campaign  scaling       — batched campaign vs sequential simulate calls
  campaign_service  jobs  — resumable campaign-as-a-service guard:
              interrupt/resume byte-identity + warm plan-cache re-run
  simstep_scale  sim cost — per-cycle cost per dispatch path (unfused
              oracle / fused auto / blocked node-tile kernel), 8×8 →
              96×96, + shard_map lane mode (parity asserted everywhere;
              budgets: ``--simstep-budget-ms`` fused 16×16,
              ``--simstep-budget64-ms`` blocked 64×64; the VMEM gate
              itself moves with ``--simstep-vmem-budget``)
  dynamics  control plane — oracle/stale/online replanning under faults
  topo_sweep  topology zoo — Q-StaR vs DOR on 3D torus / cmesh /
              express mesh / fault-region mesh (plan-table routing)
  linkload  DESIGN §3     — Q-StaR on the TPU ICI fabric
  roofline  deliverable g — per-(arch × shape × mesh) roofline table
  nrank_scale  plan cost  — numpy vs device plan builds, 8×8 → 64×64
               (the quasi-static budget; "nrank" is kept as an alias)
  certify_scale  gate cost — deadlock-certifier (CDG + Tarjan) wall per
               table, 8×8 → 32×32, budgetable via ``--certify-budget-ms``
               / CERTIFY_BUDGET_MS ("certify" is kept as an alias)
  chaos     robustness    — seeded chaos campaign: kill-and-resume
              byte-identity mid-storm, corrupted-checkpoint quarantine
              + recompute, watchdog trip on a deliberately cyclic table
  obs_report  flight recorder — telemetry-probed linkfail campaign with
              ctrl-plane tracing, rendered into ``artifacts/obs/``; the
              online-vs-stale gap must be visible from the in-sim probes
              alone, and telemetry overhead is measured (budgetable via
              ``--obs-budget-ratio`` / OBS_BUDGET_RATIO)
  ml_traffic  real ML traffic — sharded model configs lowered to
              post-SPMD HLO, collectives mapped onto the torus, derived
              matrices planned offline (greedy-refined BiDOR must beat
              XY on the MoE workloads) and simmed as a first-class
              campaign axis; budgetable via ``--ml-traffic-budget-ms``
              / ML_TRAFFIC_BUDGET_MS, grid capped via
              ``--ml-traffic-max-workloads``

Set BENCH_QUICK=0 for full-length simulations.  Run as
``PYTHONPATH=src python -m benchmarks.run [names...]``; unknown stage
names abort upfront (before anything runs) with the valid list.
``--json [PATH]`` additionally writes machine-readable per-stage
summaries (wall, ok, stage metrics) to PATH, or stdout with ``-``.
``--nrank-max-nodes`` / ``--nrank-budget-ms`` are the flag equivalents of
the ``NRANK_SCALE_MAX_NODES`` / ``NRANK_BUDGET_MS`` env knobs (the flag
wins when both are set).

Campaign stages (fig8, topo_sweep, campaign_service) run through the
campaign service (``repro.noc.service``): each job checkpoints per cell
under ``artifacts/campaigns/`` and streams its CSV.  ``--max-cells N``
budgets a run to N cells (controlled interruption); ``--resume``
continues an interrupted job bit-identically instead of starting fresh.
"""

from __future__ import annotations

import os
import sys
import time

# Expose CPU cores as XLA devices so batched campaigns shard their lane
# axis across them (repro.noc.sim.maybe_shard_states).  Must happen before
# the first jax import; a user-provided device count wins.  The flag only
# shapes the CPU backend, so on a TPU host it is harmless.
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={os.cpu_count()}"
    ).strip()

import numpy as np


def bench_campaign():
    """Batched-campaign speedup: the SAME 8 (rate, seed) points on a 4×4
    mesh, once as one jitted vmapped campaign call per algorithm and once
    as 8 sequential ``run_sim``-style calls.  Compilation is warmed for
    BOTH paths first, so the ratio is pure execution wall-clock."""
    from repro.core import build_plan, mesh2d, traffic
    from repro.noc import (Algo, CampaignSpec, SimConfig, run_campaign)
    from repro.noc.sim import run_sweep
    from .common import write_csv

    topo = mesh2d(4, 4)
    tm = traffic.uniform(topo)
    rates, seeds = (0.1, 0.25, 0.4, 0.6), (0, 1)
    cycles = 3000
    base = SimConfig(cycles=cycles, warmup=cycles // 3, drain=200)
    plan = build_plan(topo, tm)
    points = [(r, s) for r in rates for s in seeds]
    rows = []
    for algo in (Algo.XY, Algo.BIDOR):
        cfg = base.replace(algo=algo)
        table = plan.table if algo == Algo.BIDOR else None

        def sequential():
            out = []
            for r, s in points:
                out.extend(run_sweep(topo, tm, cfg, [r],
                                     bidor_table=table, seeds=[s]))
            return out

        spec = CampaignSpec(topo=topo, algos=(algo,),
                            patterns=(("uniform", tm),), rates=rates,
                            seeds=seeds, base=base, chunk=0)

        def batched():
            return run_campaign(
                spec, bidor_tables={"uniform": plan.table.choice})

        sequential()                     # warm both compile caches
        batched()
        t0 = time.time()
        seq = sequential()
        t_seq = time.time() - t0
        t0 = time.time()
        res = batched()
        t_bat = time.time() - t0
        speedup = t_seq / t_bat
        # same RNG streams -> identical statistics, batched or not
        bat = [p.result for p in res.points]
        match = all(a.injected_flits == b.injected_flits
                    and a.ejected_flits == b.ejected_flits
                    for a, b in zip(seq, bat))
        print(f"campaign {algo.name:6s} {len(points)} (rate,seed) points "
              f"x {cycles} cycles: sequential {t_seq:.2f}s, "
              f"one vmapped call {t_bat:.2f}s -> {speedup:.1f}x speedup "
              f"(stats identical: {match})")
        rows.append([algo.name, len(points), f"{t_seq:.3f}",
                     f"{t_bat:.3f}", f"{speedup:.2f}", int(match)])
        assert match, "batched campaign diverged from sequential runs"
    write_csv("campaign_speedup.csv",
              ["algo", "points", "sequential_s", "batched_s", "speedup",
               "stats_identical"], rows)


def bench_campaign_service():
    """Campaign-as-a-service guard: a small (2 algos × 2 patterns ×
    2 scenarios) job run through ``repro.noc.service``.

    Honors ``--max-cells`` / ``--resume`` like every service stage, so CI
    drives it as: interrupt after a couple of cells, resume to
    completion.  Once complete, the stage itself proves the resume
    contract — a fresh uninterrupted job of the same spec must produce a
    byte-identical ``results.csv`` — and the plan-cache contract: the
    fresh job, sharing the persistent plan cache, must make ZERO
    ``build_plans_batched`` calls.  The streamed CSV is copied to
    ``artifacts/bench/campaign_service.csv``.
    """
    from repro.core import mesh2d
    from repro.noc import (Algo, CampaignSpec, LinkFail, ReplanConfig,
                           Scenario, SimConfig)
    from .common import QUICK, out_path, run_service_campaign

    cycles = 1200 if QUICK else 6000
    topo = mesh2d(4, 4)
    spec = CampaignSpec(
        topo=topo, algos=(Algo.XY, Algo.BIDOR),
        patterns=("uniform", "transpose"), rates=(0.1, 0.3), seeds=(0,),
        base=SimConfig(cycles=cycles, warmup=cycles // 3,
                       drain=cycles // 10),
        scenarios=(
            Scenario("calm"),
            Scenario("linkfail",
                     events=(LinkFail(cycle=cycles // 2,
                                      links=((5, 6), (6, 5))),),
                     policy="oracle",
                     replan=ReplanConfig(epoch=cycles // 4))))
    res, job = run_service_campaign(spec, name="campaign_service")
    if res is None:          # interrupted by the cell budget
        return

    # fresh single-shot reference job: resumed CSV must match its bytes
    from repro.noc import run_campaign_service
    ref_res, ref_job = run_campaign_service(
        spec, root=os.path.dirname(job.dir),
        job_id=job.job_id + "-ref", resume=False, verbose=False)
    with open(job.csv_path, "rb") as f:
        got = f.read()
    with open(ref_job.csv_path, "rb") as f:
        want = f.read()
    assert got == want, (
        "resumed campaign CSV differs from the uninterrupted reference "
        f"({len(got)} vs {len(want)} bytes)")
    # ref job ran with a warm plan cache: zero plan builds is the cache
    # contract (its executor never called build_plans_batched)
    stats = ref_job.plan_cache.stats.as_dict()
    assert stats["device_builds"] == 0 and stats["hits"] > 0, (
        f"warm re-run rebuilt plans: {stats}")
    with open(out_path("campaign_service.csv"), "wb") as f:
        f.write(got)
    print(f"campaign_service: {job.status().done_cells} cells, "
          f"resume byte-identical ({len(got)} bytes CSV), warm "
          f"plan-cache stats {stats}")


def bench_simstep_scale():
    """Per-cycle simulator cost per dispatch path: the unfused jnp
    oracle vs the fused auto path vs the blocked node-tile kernel,
    8x8 -> 96x96, plus the shard_map multi-device lane mode on a 16x16
    campaign batch.  One ``simstep_cost.csv`` row per (size, path).

    Assertions, in order of importance:

    * bitwise parity of the full end state between EVERY fused path and
      the unfused oracle at EVERY size (the differential battery's
      contract, re-checked at benchmark scale), and between the sharded
      and single-device lane runners;
    * the auto dispatch ladder must resolve 64x64+ to the BLOCKED
      kernel on Pallas backends — the VMEM wall this path exists to
      break — checked symbolically on every backend;
    * where a Pallas kernel runs compiled (no backend today), the
      resolved Pallas path must be >= 2x faster per cycle at >= 16x16;
    * elsewhere the fused auto path is dense jnp and the blocked path runs
      its compiled vmap realization, so the honest claim is a
      no-regression guard (auto >= 0.8x unfused at >= 256 nodes;
      blocked >= 0.5x unfused at >= 1024 nodes, where tiling overhead
      has amortized — measured ~1.9x FASTER for both at 64x64) plus
      the optional absolute budgets ``SIMSTEP_BUDGET_MS`` (fused auto,
      16x16) and ``SIMSTEP_BUDGET64_MS`` (blocked, 64x64) as CI
      regression guards.

    ``SIMSTEP_MAX_NODES`` caps the sweep (CI smoke); a capped run skips
    the committed-CSV rewrite, like ``nrank_scale``.  ``BENCH_QUICK``
    shortens the cycle counts.  ``SIMSTEP_VMEM_BUDGET`` (flag
    ``--simstep-vmem-budget``) moves the VMEM gate itself.
    """
    import jax
    import numpy as np
    from repro.core import mesh2d, traffic
    from repro.kernels.simstep import ops as simstep_ops
    from repro.noc.simconfig import Algo, SimConfig
    from repro.noc import sim
    from .common import write_csv

    max_nodes = int(os.environ.get("SIMSTEP_MAX_NODES", "0"))
    budget = float(os.environ.get("SIMSTEP_BUDGET_MS", "0"))
    budget64 = float(os.environ.get("SIMSTEP_BUDGET64_MS", "0"))
    quick = os.environ.get("BENCH_QUICK", "0") not in ("0", "")
    # the >= 2x claims are for a compiled Pallas kernel, which no backend
    # lowers today (simstep_ops.backend_supports_pallas)
    accel = simstep_ops.backend_supports_pallas()
    cases = ([(8, 120), (16, 90), (32, 40), (64, 12), (96, 6)] if quick
             else [(8, 400), (16, 300), (32, 120), (64, 48), (96, 24)])
    rows = []
    per_cycle: dict[tuple[int, str], float] = {}

    def timed_run(runner, tables, meta, cfg, points, cycles):
        out = runner(tables, sim.make_states(meta, cfg, points))
        jax.block_until_ready(out)                      # compile warm
        best = float("inf")
        for _ in range(3):
            states = sim.make_states(meta, cfg, points)
            t0 = time.perf_counter()
            out = runner(tables, states)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        return jax.device_get(out), best / cycles * 1e3

    def bench_tile(meta, cfg):
        """The tile the blocked row runs: the auto choice, demoted to
        the largest PROPER divisor when the whole network fits one tile
        (a grid of one would not exercise the stitching)."""
        n = meta["N"]
        tile = simstep_ops.auto_tile_nodes(meta, cfg)
        if tile in (0, n):
            tile = max(d for d in range(1, n) if n % d == 0)
        return tile

    for k, cycles in cases:
        topo = mesh2d(k, k)
        n = topo.num_nodes
        if max_nodes and n > max_nodes:
            continue
        tm = traffic.uniform(topo)
        cfg0 = SimConfig(algo=Algo.XY, cycles=cycles,
                         warmup=cycles // 3, use_kernel=False)
        tables, meta = sim.build_tables(topo, tm, None, cfg0.num_vcs)
        auto_path, auto_tile, _ = simstep_ops.resolve_path(
            meta, cfg0.replace(use_kernel=True))
        tile = bench_tile(meta, cfg0)
        if n >= 4096:
            # the acceptance bar: past the VMEM wall the auto ladder on
            # a Pallas backend must land on the blocked kernel, never
            # the dense fallback (checked symbolically on CPU too)
            sym, sym_tile, _ = simstep_ops.resolve_path(
                meta, cfg0.replace(use_kernel=True), supported=True)
            assert sym == "blocked" and sym_tile > 0, (
                f"{k}x{k}: auto ladder resolved to {sym} "
                f"(tile={sym_tile}); the blocked kernel must own "
                f"this size on Pallas backends")
        paths = [
            ("unfused", 0, cfg0),
            (f"fused_{auto_path}", auto_tile,
             cfg0.replace(use_kernel=True)),
            ("blocked", tile,
             cfg0.replace(use_kernel=True, sim_tile_nodes=tile)),
        ]
        outs = {}
        for path, ptile, cfg in paths:
            runner = sim.get_runner(meta, cfg, cycles)
            outs[path], ms = timed_run(runner, tables, meta, cfg,
                                       [(0.3, 0)], cycles)
            per_cycle[(k, path)] = ms
            su = per_cycle[(k, "unfused")] / ms
            ident = all(np.array_equal(outs["unfused"][x], outs[path][x])
                        for x in outs["unfused"])
            assert ident, f"{k}x{k}/{path}: diverged from unfused"
            print(f"simstep_scale,{k}x{k},{path},{ms:.3f}ms/cyc,"
                  f"speedup={su:.2f}x,identical={ident}")
            rows.append([f"mesh{k}x{k}", n, cycles, path, ptile,
                         f"{ms:.4f}", f"{su:.3f}", int(ident)])
        su_auto = (per_cycle[(k, "unfused")]
                   / per_cycle[(k, f"fused_{auto_path}")])
        su_blocked = per_cycle[(k, "unfused")] / per_cycle[(k, "blocked")]
        if accel and auto_path in ("whole", "blocked") and n >= 256:
            # a Pallas kernel actually ran: the fusion claim
            assert su_auto >= 2.0, (
                f"{k}x{k}: kernel path must be >= 2x on an "
                f"accelerator backend (got {su_auto:.2f}x)")
        elif n >= 256:
            # CPU fallback (dense body): no-regression guard with
            # noise headroom
            assert su_auto >= 0.8, (
                f"{k}x{k}: fused fallback regressed past the "
                f"noise guard ({su_auto:.2f}x)")
        if n >= 1024:
            assert su_blocked >= (2.0 if accel else 0.5), (
                f"{k}x{k}: blocked path regressed past the guard "
                f"({su_blocked:.2f}x)")
    auto16 = next((v for (k, p), v in per_cycle.items()
                   if k == 16 and p.startswith("fused_")), None)
    if budget and auto16 is not None:
        assert auto16 <= budget, (
            f"fused 16x16 per-cycle cost {auto16:.3f}ms "
            f"over the {budget:.1f}ms budget")
    if budget64 and (64, "blocked") in per_cycle:
        assert per_cycle[(64, "blocked")] <= budget64, (
            f"blocked 64x64 per-cycle cost "
            f"{per_cycle[(64, 'blocked')]:.3f}ms over the "
            f"{budget64:.1f}ms budget")

    # ---- shard_map mega-campaign mode: lanes across local devices ---- #
    ndev = jax.device_count()
    if (not max_nodes or max_nodes >= 256) and ndev > 1:
        topo = mesh2d(16, 16)
        tm = traffic.uniform(topo)
        cycles = 200
        lanes = [(r, s) for r in (0.1, 0.2, 0.3, 0.4)
                 for s in range(max(2, ndev // 2))]
        lanes = lanes[:len(lanes) - len(lanes) % ndev] or \
            [(0.3, s) for s in range(ndev)]
        cfg = SimConfig(algo=Algo.XY, cycles=cycles, warmup=cycles // 3)
        tables, meta = sim.build_tables(topo, tm, None, cfg.num_vcs)
        res = {}
        for md in (False, True):
            runner = sim.get_runner(meta, cfg, cycles,
                                    num_lanes=len(lanes), multi_device=md)
            res[md] = timed_run(runner, tables, meta, cfg, lanes, cycles)
        ident = all(np.array_equal(res[False][0][x], res[True][0][x])
                    for x in res[False][0])
        assert ident, "sharded lanes diverged from single-device"
        su = res[False][1] / res[True][1]
        print(f"simstep_scale,shard16x16,{len(lanes)} lanes x {ndev} "
              f"devices: single={res[False][1]:.3f}ms/cyc "
              f"sharded={res[True][1]:.3f}ms/cyc -> {su:.2f}x, "
              f"identical={ident}")
        case = f"shard16x16_l{len(lanes)}d{ndev}"
        rows.append([case, 256, cycles, "lanes_single", 0,
                     f"{res[False][1]:.4f}", "1.000", 1])
        rows.append([case, 256, cycles, "lanes_sharded", 0,
                     f"{res[True][1]:.4f}", f"{su:.3f}", int(ident)])

    if max_nodes:
        print(f"simstep_scale: sweep capped at {max_nodes} nodes; "
              "skipping simstep_cost.csv rewrite")
    else:
        write_csv("simstep_cost.csv",
                  ["case", "nodes", "cycles", "path", "tile_nodes",
                   "ms_per_cycle", "speedup_vs_unfused", "identical"],
                  rows)
    return {
        "backend": jax.default_backend(),
        "vmem_budget_bytes": simstep_ops.vmem_budget_bytes(),
        "budget_ms": budget or None, "budget64_ms": budget64 or None,
        "per_cycle_ms": {f"{k}x{k}/{p}": round(v, 4)
                         for (k, p), v in sorted(per_cycle.items())},
    }


def bench_nrank_scale():
    """Plan-build cost at scale: the numpy host pipeline vs the
    device-resident ``build_plan_fast``, cold (statics + jit compile) vs
    warm — the 'ample time offline' budget of paper §3.1, which the
    online re-planner turns into a latency requirement.

    The numpy path only runs where it is tractable (≤ 256 nodes); the
    device path must beat it at ≥ 256 nodes (asserted) and the 64×64
    stretch case runs only when the measured 32×32 warm build predicts
    it under 60 s.  ``NRANK_SCALE_MAX_NODES`` caps the sweep (CI smoke).
    """
    import numpy as np
    from repro.core import (build_plan, build_plan_fast, mesh2d,
                            mesh2d_edge_io, torus, traffic)
    from .common import write_csv

    max_nodes = int(os.environ.get("NRANK_SCALE_MAX_NODES", "0"))
    cases = [("mesh5x5", mesh2d(5, 5)),
             ("edgeio5x5", mesh2d_edge_io(5, 5)),
             ("torus8x8", torus(8, 8)),
             ("torus16x16", torus(16, 16)),
             ("torus32x32", torus(32, 32))]
    rows = []
    device_warm: dict[str, float] = {}
    numpy_ms: dict[str, float] = {}

    def one_case(name, topo):
        t = traffic.uniform(topo)
        t0 = time.time()
        plan = build_plan_fast(topo, t)
        cold = time.time() - t0
        warm = min(_timed(build_plan_fast, topo, t)[1] for _ in range(2))
        device_warm[name] = warm * 1e3
        rows.append([name, topo.num_nodes, "device", f"{cold * 1e3:.1f}",
                     f"{warm * 1e3:.1f}", plan.nrank.iterations])
        print(f"nrank_scale,{name},device,cold={cold * 1e3:.0f}ms,"
              f"warm={warm * 1e3:.0f}ms,iters={plan.nrank.iterations}")
        if topo.num_nodes <= 256:
            ref, host = _timed(build_plan, topo, t)
            numpy_ms[name] = host * 1e3
            rows.append([name, topo.num_nodes, "numpy",
                         f"{host * 1e3:.1f}", f"{host * 1e3:.1f}",
                         ref.nrank.iterations])
            print(f"nrank_scale,{name},numpy,{host * 1e3:.0f}ms")
            assert np.array_equal(ref.table.choice, plan.table.choice), (
                f"{name}: device choice table diverged from numpy oracle")
        return plan

    def _timed(fn, *args):
        t0 = time.time()
        out = fn(*args)
        return out, time.time() - t0

    for name, topo in cases:
        if max_nodes and topo.num_nodes > max_nodes:
            continue
        one_case(name, topo)

    w32 = device_warm.get("torus32x32")
    if w32 is not None and w32 * 64 < 60e3 and not (
            max_nodes and 4096 > max_nodes):
        one_case("torus64x64", torus(64, 64))

    if "torus16x16" in numpy_ms:
        np_ms, dev_ms = numpy_ms["torus16x16"], device_warm["torus16x16"]
        print(f"nrank_scale: 16x16 device {dev_ms:.0f}ms vs numpy "
              f"{np_ms:.0f}ms -> {np_ms / dev_ms:.1f}x")
        assert dev_ms < np_ms, (
            "device plan build must beat numpy at >= 256 nodes "
            f"({dev_ms:.0f}ms vs {np_ms:.0f}ms)")
        budget = float(os.environ.get("NRANK_BUDGET_MS", "0"))
        if budget:
            assert dev_ms <= budget, (
                f"16x16 warm plan build {dev_ms:.0f}ms over the "
                f"{budget:.0f}ms budget")
    if max_nodes:
        # capped smoke run (CI): don't overwrite the committed full-sweep
        # artifact with a truncated one
        print(f"nrank_scale: sweep capped at {max_nodes} nodes; "
              "skipping nrank_cost.csv rewrite")
    else:
        write_csv("nrank_cost.csv",
                  ["topology", "nodes", "path", "cold_ms", "warm_ms",
                   "iters"], rows)


def bench_certify_scale():
    """Deadlock-certifier cost at scale: CDG build + Tarjan SCC over
    freshly planned tables, 8×8 → 32×32 meshes plus a wrapped torus
    (dateline layers), warm best-of-3 per size.

    Every table must certify clean (the gate runs on every plan-producing
    path, so its verdict here is a tautology check — a non-clean verdict
    means the gate itself regressed).  ``CERTIFY_BUDGET_MS``
    (``--certify-budget-ms``) asserts the WORST measured certify wall
    stays under budget — the control-plane requirement: the gate rides
    every online replan, so it must be cheap relative to the plan build.
    ``CERTIFY_MAX_NODES`` caps the sweep (CI smoke; skips the committed
    CSV rewrite like ``nrank_scale``).
    """
    from repro.core import (build_plan_fast, certify_table, mesh2d, torus,
                            traffic)
    from .common import write_csv

    max_nodes = int(os.environ.get("CERTIFY_MAX_NODES", "0"))
    budget = float(os.environ.get("CERTIFY_BUDGET_MS", "0"))
    cases = [("mesh8x8", mesh2d(8, 8)),
             ("torus8x8", torus(8, 8)),
             ("mesh16x16", mesh2d(16, 16)),
             ("mesh32x32", mesh2d(32, 32))]
    rows = []
    worst = ("", 0.0)
    for name, topo in cases:
        if max_nodes and topo.num_nodes > max_nodes:
            continue
        tm = traffic.uniform(topo)
        plan = build_plan_fast(topo, tm)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            cert = certify_table(topo, plan.table, traffic=tm,
                                 w_nr=plan.nrank.w_nr)
            best = min(best, (time.perf_counter() - t0) * 1e3)
        assert cert.verdict == "clean", (
            f"{name}: planned table no longer certifies clean "
            f"({cert.verdict}, {cert.cyclic_nodes} cyclic nodes)")
        if best > worst[1]:
            worst = (name, best)
        print(f"certify_scale,{name},nodes={topo.num_nodes},"
              f"cdg_nodes={cert.cdg_nodes},cdg_edges={cert.cdg_edges},"
              f"verdict={cert.verdict},warm={best:.1f}ms")
        rows.append([name, topo.num_nodes, cert.cdg_nodes,
                     cert.cdg_edges, cert.verdict, f"{best:.2f}"])
    if budget and worst[0]:
        assert worst[1] <= budget, (
            f"certify wall {worst[1]:.1f}ms on {worst[0]} over the "
            f"{budget:.0f}ms budget")
    if max_nodes:
        print(f"certify_scale: sweep capped at {max_nodes} nodes; "
              "skipping certify_cost.csv rewrite")
    else:
        write_csv("certify_cost.csv",
                  ["topology", "nodes", "cdg_nodes", "cdg_edges",
                   "verdict", "warm_ms"], rows)
    return {"worst_case": worst[0], "worst_ms": round(worst[1], 2),
            "sizes": len(rows)}


def bench_chaos():
    """Chaos smoke: the robustness stack end to end, fixed seeds.

    1. A chaos campaign (two seeded storm schedules + a calm control,
       :mod:`repro.noc.chaos`) is interrupted after every cell and
       resumed; the final ``results.csv`` must be byte-identical to an
       uninterrupted reference job of the same spec.
    2. One completed cell's npz is then truncated in place; the next
       resume must quarantine it (``cell_quarantined`` in
       ``metrics.jsonl``), recompute, and reproduce the same CSV bytes.
    3. A deliberately cyclic ring table (the certifier rejects it; here
       force-fed to the simulator) must trip the stall watchdog
       (deadlock trips > 0) and still drain via the escape lane.
    """
    from repro.core import BiDORTable, build_plan, mesh2d, traffic
    from repro.noc import (Algo, CampaignSpec, ChaosConfig, ReplanConfig,
                           Scenario, SimConfig, chaos_scenarios,
                           run_campaign_service, run_sim)
    from repro.obs.report import load_metrics
    from .common import QUICK, SERVICE_ROOT, out_path

    cycles = 2600 if QUICK else 8000
    topo = mesh2d(4, 4)
    plan = build_plan(topo, traffic.uniform(topo))
    cc = ChaosConfig(start=cycles // 4, horizon=cycles, flap_storms=1,
                     flap_links=2, flap_bursts=2,
                     flap_period=cycles // 12, region_failures=1,
                     drift_events=1)
    rc = ReplanConfig(epoch=cycles // 6, max_shed=0.5)
    spec = CampaignSpec(
        topo=topo, algos=(Algo.BIDOR,), patterns=("uniform",),
        rates=(0.3,), seeds=(0,),
        base=SimConfig(cycles=cycles, warmup=cycles // 4,
                       drain=cycles // 10, watchdog=True),
        scenarios=(Scenario("calm"),
                   *chaos_scenarios(topo, [0, 1], replan=rc,
                                    base=cc)))
    tables = {"uniform": plan.table.choice}

    # ---- 1. kill-and-resume mid-storm, byte-identical ---- #
    kwargs = dict(root=SERVICE_ROOT, bidor_tables=tables)
    interrupts = 0
    while True:
        res, job = run_campaign_service(spec, job_id="chaos-smoke",
                                        max_cells=1, **kwargs)
        if res is not None:
            break
        interrupts += 1
        assert interrupts <= 8, "chaos job failed to converge"
    ref_res, ref_job = run_campaign_service(
        spec, job_id="chaos-smoke-ref", resume=False, **kwargs)
    with open(job.csv_path, "rb") as f:
        got = f.read()
    with open(ref_job.csv_path, "rb") as f:
        want = f.read()
    assert got == want, (
        f"chaos kill-and-resume CSV diverged ({len(got)} vs "
        f"{len(want)} bytes)")

    # ---- 2. quarantined-checkpoint recovery ---- #
    victim = job.cells[1]
    path = job._cell_path(victim)
    with open(path, "rb") as f:
        blob = f.read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 2])
    res2, job2 = run_campaign_service(spec, job_id="chaos-smoke",
                                      **kwargs)
    assert res2 is not None
    quar = [r for r in load_metrics(job2.metrics_path)
            if r["event"] == "cell_quarantined"]
    assert [r["cell"] for r in quar] == [victim.slug], (
        f"expected exactly one quarantine of {victim.slug}, got {quar}")
    assert os.path.exists(os.path.join(
        job2.quarantine_dir, f"{victim.slug}.npz"))
    with open(job2.csv_path, "rb") as f:
        assert f.read() == want, "post-quarantine CSV diverged"

    # ---- 3. watchdog trips on a deliberately cyclic table ---- #
    ring = mesh2d(2, 2)
    order = [0, 1, 3, 2]
    nxt = {order[i]: order[(i + 1) % 4] for i in range(4)}
    neigh = np.asarray(ring.neighbor_table)
    pt = np.zeros((1, 4, 4), np.int8)
    for cur in range(4):
        for dst in range(4):
            pt[0, cur, dst] = (
                ring.port_local if cur == dst else
                next(k for k in range(neigh.shape[1])
                     if neigh[cur, k] == nxt[cur]))
    cyclic = BiDORTable(choice=np.zeros((4, 4), np.int8),
                        orders=((0, 1),),
                        costs=np.zeros((1, 4, 4), np.float32),
                        port_tables=pt)
    wd_cfg = SimConfig(algo=Algo.BIDOR, cycles=3000, warmup=500,
                       injection_rate=0.6, num_vcs=2, use_kernel=False,
                       watchdog=True, wd_stall_cycles=32)
    r, wd = run_sim(ring, traffic.uniform(ring), wd_cfg, cyclic,
                    return_watchdog=True)
    assert wd is not None and wd.deadlock_trips > 0, (
        "watchdog failed to trip on a cyclic ring table")
    assert r.ejected_flits > 0, "escape recovery delivered nothing"

    with open(out_path("chaos_smoke.csv"), "wb") as f:
        f.write(got)
    metrics = {"cells": len(job.cells), "interrupts": interrupts,
               "csv_bytes": len(got), "quarantined": len(quar),
               "wd_deadlock_trips": wd.deadlock_trips,
               "wd_max_stall": wd.max_stall,
               "escape_ejected": r.ejected_flits}
    print("chaos:", metrics)
    return metrics


def bench_obs_report():
    """Flight recorder end-to-end: a telemetry-probed, ctrl-traced
    linkfail campaign (stale vs online policies), rendered into
    ``artifacts/obs/<job_id>/``.

    Asserts, from the recorded artifacts alone (no SimResult access):

    * the Chrome-trace file is Perfetto-parseable and schema-valid, and
      records the drift→replan→hot-swap chain with wall timings;
    * the in-sim probes reproduce the dynamics story: after the online
      policy's replan, its time-resolved peak-link-load trajectory drops
      below the stale policy's (which stays pinned at the saturated
      degraded link);
    * telemetry overhead: the probed run's per-cycle cost vs the same
      cell with telemetry off — reported always, asserted under
      ``OBS_BUDGET_RATIO`` (``--obs-budget-ratio``) when set.

    Returns the stage's metrics dict (surfaced by ``--json``).
    """
    import json
    import jax
    from repro.core import mesh2d, traffic
    from repro.noc import (Algo, CampaignSpec, LinkFail, ReplanConfig,
                           Scenario, SimConfig)
    from repro.noc import sim
    from repro.obs.report import render_job
    from repro.obs.trace import read_trace, validate_events
    from .common import QUICK, run_service_campaign

    cycles = 900 if QUICK else 4000
    epoch = cycles // 6
    topo = mesh2d(4, 4)
    fail_cycle = 2 * epoch
    fail = LinkFail(cycle=fail_cycle, links=((5, 6), (6, 5)))
    base = SimConfig(cycles=cycles, warmup=epoch, drain=epoch,
                     injection_rate=0.3, telemetry=True, tel_slots=18)
    spec = CampaignSpec(
        topo=topo, algos=(Algo.BIDOR,), patterns=("transpose",),
        rates=(0.3,), seeds=(0,), base=base,
        scenarios=(
            Scenario("stale", events=(fail,), policy="stale",
                     replan=ReplanConfig(epoch=epoch)),
            Scenario("online", events=(fail,), policy="online",
                     replan=ReplanConfig(epoch=epoch))))
    res, job = run_service_campaign(spec, name="obs_report", trace=True)
    if res is None:          # interrupted by the cell budget
        return None

    # ---- render the job's artifacts ---- #
    obs_root = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                            "obs")
    summary = render_job(job.dir, os.path.join(obs_root, job.job_id))

    # ---- trace plane: Perfetto-parseable, replan chain recorded ---- #
    events = read_trace(job.trace_path)
    problems = validate_events(events)
    assert not problems, f"trace schema problems: {problems[:5]}"
    names = {e["name"] for e in events}
    assert {"epoch", "LinkFail", "replan", "hot_swap"} <= names, (
        f"ctrl-plane chain missing from trace: have {sorted(names)}")
    replans = [e for e in events if e["name"] == "replan"]
    assert all(e.get("dur", 0) > 0 for e in replans), (
        "replan spans must carry wall durations")

    # ---- probe plane: the online-vs-stale gap, from telemetry only --- #
    tels = {k.scenario: job.cell_telemetry(k) for k in job.cells}
    assert all(t is not None for t in tels.values()), "telemetry missing"
    starts = tels["stale"].slot_starts()
    # compare after the online replan has settled (one epoch past it)
    post = [s for s in tels["stale"].active_slots()
            if starts[s] >= fail_cycle + epoch]
    assert post, "no telemetry slots after the replan window"
    stale_mean = float(tels["stale"].peak_link_load()[0][post].mean())
    online_mean = float(tels["online"].peak_link_load()[0][post].mean())
    print(f"obs_report: post-replan peak link load (probes alone): "
          f"stale {stale_mean:.3f} vs online {online_mean:.3f} over "
          f"{len(post)} slots")
    assert online_mean < stale_mean - 0.02, (
        f"online replan gap not visible from probes: "
        f"stale {stale_mean:.3f} vs online {online_mean:.3f}")

    # ---- overhead: telemetry on vs off, same cell ---- #
    tm = traffic.uniform(topo)
    per_cycle = {}
    for tel_on in (False, True):
        cfg = SimConfig(algo=Algo.XY, cycles=300, warmup=100,
                        telemetry=tel_on)
        tables, meta = sim.build_tables(topo, tm, None, cfg.num_vcs)
        runner = sim.get_runner(meta, cfg, 300)
        out = runner(tables, sim.make_states(meta, cfg, [(0.3, 0)]))
        jax.block_until_ready(out)                   # compile warm
        best = float("inf")
        for _ in range(3):
            states = sim.make_states(meta, cfg, [(0.3, 0)])
            t0 = time.perf_counter()
            jax.block_until_ready(runner(tables, states))
            best = min(best, time.perf_counter() - t0)
        per_cycle[tel_on] = best / 300 * 1e3
    ratio = per_cycle[True] / per_cycle[False]
    print(f"obs_report: telemetry overhead {per_cycle[False]:.4f} -> "
          f"{per_cycle[True]:.4f} ms/cycle ({ratio:.2f}x)")
    budget = float(os.environ.get("OBS_BUDGET_RATIO", "0"))
    if budget:
        assert ratio <= budget, (
            f"telemetry overhead {ratio:.2f}x over the {budget:.2f}x "
            f"budget")

    metrics = {"trace_events": len(events), "replans": len(replans),
               "stale_peak_mean": round(stale_mean, 4),
               "online_peak_mean": round(online_mean, 4),
               "telemetry_overhead_ratio": round(ratio, 3),
               "traj_rows": summary["traj_rows"],
               "report": os.path.join(summary["out_dir"], "report.md")}
    print("obs_report:", json.dumps(metrics, sort_keys=True))
    return metrics


def bench_ml_traffic():
    """Real ML traffic end to end: sharded model configs are lowered,
    their post-SPMD collectives extracted from HLO, mapped onto a
    ``torus(2, 4)`` ICI fabric, and the derived matrices driven through
    the offline planner AND the flit-level campaign simulator.

    Grid: two MoE models (qwen2-moe, dbrx — expert-parallel all-to-all
    makes demand lumpy) and two dense models (internlm2, stablelm —
    ring-collective dominated).  Per workload:

    * the derived matrix is planned offline; the greedy-refined BiDOR
      table (``greedy_refine`` seeded from best-of(plan, XY)) must beat
      plain XY on max-link-load STRICTLY for the MoE workloads — the
      paper's claim on real traffic — and never lose on the dense ones;
    * every refined table is re-certified deadlock-free before it is
      allowed near the simulator;
    * one campaign job (XY vs BiDOR × rates) runs through the campaign
      service with the workloads as first-class axis entries; MoE cells
      use the refined tables, dense cells exercise the plan-cache +
      certifier-gate path; sim p50/p99 latencies are reported per
      workload × algo.

    ``ML_TRAFFIC_MAX_WORKLOADS`` (``--ml-traffic-max-workloads``) caps
    the grid (CI smoke runs the first 2 — the asserted MoE pair).
    ``ML_TRAFFIC_BUDGET_MS`` (``--ml-traffic-budget-ms``) asserts the
    worst non-cached HLO→matrix derivation wall stays under budget,
    mirroring ``certify_scale``.  Derived matrices are cached as npz
    under ``artifacts/bench/mltraffic/`` (uploaded by CI).
    """
    from repro.core import (bidor, build_plan, certify_table,
                            link_load_stats, torus)
    from repro.core.bidor import greedy_refine
    from repro.noc import Algo, CampaignSpec, SimConfig, WorkloadSpec
    from repro.noc.mltraffic import derive_workload
    from .common import QUICK, out_path, run_service_campaign, write_csv

    max_wl = int(os.environ.get("ML_TRAFFIC_MAX_WORKLOADS", "0"))
    budget = float(os.environ.get("ML_TRAFFIC_BUDGET_MS", "0"))
    cache_dir = out_path("mltraffic")

    # MoE entries first so the CI smoke cap (=2) still exercises the
    # BiDOR-beats-XY assertion.  (spec, moe?) pairs.
    grid = [
        (WorkloadSpec("qwen2-moe-a2.7b", data=1, model=8, moe_pad_to=8,
                      phases=("decode",),
                      label="qwen2-moe@1x8:decode"), True),
        (WorkloadSpec("dbrx-132b", data=1, model=8, moe_pad_to=8,
                      phases=("train", "decode"),
                      label="dbrx@1x8:step"), True),
        (WorkloadSpec("internlm2-1.8b", data=1, model=8,
                      phases=("train", "decode"),
                      label="internlm2@1x8:step"), False),
        (WorkloadSpec("stablelm-3b", data=1, model=8,
                      phases=("train", "decode"),
                      label="stablelm@1x8:step"), False),
    ]
    if max_wl:
        grid = grid[:max_wl]

    topo = torus(2, 4)
    n = topo.num_nodes
    xy = bidor(topo, np.zeros(n))          # zero N-Rank weights -> XY

    def mx(tm, table):
        return link_load_stats(topo, tm, table)["max"]

    wls, tables, rows = [], {}, []
    worst = ("", 0.0)
    for spec, moe in grid:
        t0 = time.perf_counter()
        wl = derive_workload(spec, cache_dir=cache_dir)
        wall_ms = (time.perf_counter() - t0) * 1e3
        cached = wall_ms < 100.0           # npz load, no lowering
        if not cached and wall_ms > worst[1]:
            worst = (wl.name, wall_ms)
        tm = wl.matrix_for(topo)
        plan = build_plan(topo, tm)
        start = plan.table if mx(tm, plan.table) <= mx(tm, xy) else xy
        ref = greedy_refine(topo, tm, start, sweeps=3)
        m_xy, m_bd, m_rf = (mx(tm, t) for t in (xy, plan.table, ref))
        win = (m_xy - m_rf) / m_xy
        cert = certify_table(topo, ref, traffic=tm)
        assert cert.verdict == "clean", (
            f"{wl.name}: refined table failed certification "
            f"({cert.verdict})")
        assert m_rf <= m_xy + 1e-12, (
            f"{wl.name}: refined table lost to XY "
            f"({m_rf:.4f} vs {m_xy:.4f})")
        if moe:
            # the paper's claim on real traffic: expert-parallel
            # all-to-all demand is lumpy enough for per-pair XY/YX
            # choice to beat plain DOR (measured ~+12% on this grid)
            assert m_rf < m_xy * (1.0 - 1e-6), (
                f"{wl.name}: BiDOR must strictly beat XY on the MoE "
                f"workload ({m_rf:.4f} vs {m_xy:.4f})")
            tables[wl.name] = ref.choice
        ops = sum(wl.meta.get("collective_op_counts", {}).values())
        print(f"ml_traffic,{wl.name},derive={wall_ms:.0f}ms"
              f"{'(cached)' if cached else ''},ops={ops},"
              f"xy={m_xy:.4f},bidor={m_bd:.4f},refined={m_rf:.4f},"
              f"win={win:+.1%},cert={cert.verdict}")
        wls.append(wl)
        rows.append([wl.name, spec.arch, "+".join(spec.phases),
                     int(moe), f"{wall_ms:.0f}", int(cached),
                     f"{m_xy:.4f}", f"{m_bd:.4f}", f"{m_rf:.4f}",
                     f"{win:.4f}", cert.verdict])
    if budget and worst[0]:
        assert worst[1] <= budget, (
            f"ml_traffic derivation wall {worst[1]:.0f}ms on "
            f"{worst[0]} over the {budget:.0f}ms budget")

    # ---- campaign: derived matrices as a first-class axis ---- #
    cycles = 200 if QUICK else 2000
    spec = CampaignSpec(
        topo=topo, algos=(Algo.XY, Algo.BIDOR), patterns=(),
        workloads=tuple(wls), rates=(0.1, 0.3), seeds=(0,),
        base=SimConfig(cycles=cycles, warmup=cycles // 4,
                       drain=cycles // 10))
    res, job = run_service_campaign(spec, name="ml_traffic",
                                    bidor_tables=tables or None)
    if res is None:          # interrupted by the cell budget
        return None

    lat_rows, sim_metrics = [], {}
    for wl in wls:
        for algo in (Algo.XY, Algo.BIDOR):
            pts = res.select(workload=wl.name, algo=algo)
            assert pts, f"no campaign points for {wl.name}/{algo.name}"
            p50 = float(np.mean([p.result.p50_latency for p in pts]))
            p99 = float(np.mean([p.result.p99_latency for p in pts]))
            lat_rows.append([wl.name, algo.name, len(pts),
                             f"{p50:.1f}", f"{p99:.1f}"])
            sim_metrics[f"{wl.name}/{algo.name}"] = {
                "p50": round(p50, 1), "p99": round(p99, 1)}
            print(f"ml_traffic,sim,{wl.name},{algo.name},"
                  f"p50={p50:.1f},p99={p99:.1f}")

    write_csv("ml_traffic.csv",
              ["workload", "arch", "phases", "moe", "derive_ms",
               "cached", "xy_max", "bidor_max", "refined_max",
               "refined_win", "cert"], rows)
    write_csv("ml_traffic_sim.csv",
              ["workload", "algo", "points", "p50_latency",
               "p99_latency"], lat_rows)
    moe_wins = {r[0]: float(r[9]) for r in rows if r[3]}
    metrics = {"workloads": len(wls), "cells": len(job.cells),
               "moe_wins": {k: round(v, 3) for k, v in moe_wins.items()},
               "worst_derive_ms": round(worst[1], 0),
               "worst_derive_wl": worst[0]}
    print("ml_traffic:", metrics)
    return metrics


def _stage_fig1():
    from . import fig1_load
    fig1_load.main()


def _stage_table1():
    from . import table1_lcv
    table1_lcv.main()


def _stage_fig8():
    from . import fig8_synthetic
    fig8_synthetic.main()


def _stage_fig9():
    from . import fig9_realistic
    fig9_realistic.main()


def _stage_dynamics():
    from . import dynamics
    dynamics.main()


def _stage_topo_sweep():
    from . import topo_sweep
    topo_sweep.main()


def _stage_linkload():
    from . import linkload
    linkload.main()


def _stage_roofline():
    from . import roofline
    roofline.main()


# registry: stage name → runner, in default execution order
STAGES = {
    "fig1": _stage_fig1,
    "table1": _stage_table1,
    "fig8": _stage_fig8,
    "fig9": _stage_fig9,
    "campaign": bench_campaign,
    "campaign_service": bench_campaign_service,
    "simstep_scale": bench_simstep_scale,
    "dynamics": _stage_dynamics,
    "topo_sweep": _stage_topo_sweep,
    "linkload": _stage_linkload,
    "roofline": _stage_roofline,
    "nrank_scale": bench_nrank_scale,
    "certify_scale": bench_certify_scale,
    "obs_report": bench_obs_report,
    "chaos": bench_chaos,
    "ml_traffic": bench_ml_traffic,
}
ALIASES = {"nrank": "nrank_scale", "certify": "certify_scale"}


def main(argv: list[str] | None = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(
        prog="benchmarks.run", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("stages", nargs="*", metavar="stage",
                    help=f"stages to run (default: all); one of "
                         f"{', '.join([*STAGES, *ALIASES])}")
    ap.add_argument("--nrank-max-nodes", type=int, default=None,
                    help="cap the nrank_scale sweep at this many nodes "
                         "(flag form of NRANK_SCALE_MAX_NODES)")
    ap.add_argument("--nrank-budget-ms", type=float, default=None,
                    help="assert the warm 16x16 plan build stays under "
                         "this budget (flag form of NRANK_BUDGET_MS)")
    ap.add_argument("--simstep-max-nodes", type=int, default=None,
                    help="cap the simstep_scale sweep at this many nodes "
                         "(flag form of SIMSTEP_MAX_NODES)")
    ap.add_argument("--simstep-budget-ms", type=float, default=None,
                    help="assert the fused 16x16 per-cycle cost stays "
                         "under this budget (flag form of "
                         "SIMSTEP_BUDGET_MS)")
    ap.add_argument("--simstep-budget64-ms", type=float, default=None,
                    help="assert the blocked 64x64 per-cycle cost stays "
                         "under this budget (flag form of "
                         "SIMSTEP_BUDGET64_MS)")
    ap.add_argument("--simstep-vmem-budget", type=int, default=None,
                    help="on-chip byte budget for the simstep VMEM "
                         "dispatch gate (flag form of "
                         "SIMSTEP_VMEM_BUDGET)")
    ap.add_argument("--resume", action="store_true",
                    help="resume interrupted campaign-service jobs, "
                         "skipping completed cells bit-identically "
                         "(flag form of CAMPAIGN_RESUME=1)")
    ap.add_argument("--max-cells", type=int, default=None,
                    help="execute at most N campaign cells per service "
                         "job then stop (controlled interruption; flag "
                         "form of CAMPAIGN_MAX_CELLS)")
    ap.add_argument("--certify-max-nodes", type=int, default=None,
                    help="cap the certify_scale sweep at this many nodes "
                         "(flag form of CERTIFY_MAX_NODES)")
    ap.add_argument("--certify-budget-ms", type=float, default=None,
                    help="assert the worst certify wall stays under this "
                         "budget (flag form of CERTIFY_BUDGET_MS)")
    ap.add_argument("--obs-budget-ratio", type=float, default=None,
                    help="assert the telemetry-on per-cycle cost stays "
                         "under this multiple of telemetry-off (flag "
                         "form of OBS_BUDGET_RATIO)")
    ap.add_argument("--ml-traffic-max-workloads", type=int, default=None,
                    help="cap the ml_traffic workload grid at the first "
                         "N entries (flag form of "
                         "ML_TRAFFIC_MAX_WORKLOADS)")
    ap.add_argument("--ml-traffic-budget-ms", type=float, default=None,
                    help="assert the worst non-cached HLO-to-matrix "
                         "derivation wall stays under this budget (flag "
                         "form of ML_TRAFFIC_BUDGET_MS)")
    ap.add_argument("--json", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="write machine-readable per-stage summaries "
                         "(JSON) to PATH; '-' or no value -> stdout")
    args = ap.parse_args(argv if argv is not None else sys.argv[1:])
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if args.nrank_max_nodes is not None:
        os.environ["NRANK_SCALE_MAX_NODES"] = str(args.nrank_max_nodes)
    if args.nrank_budget_ms is not None:
        os.environ["NRANK_BUDGET_MS"] = str(args.nrank_budget_ms)
    if args.simstep_max_nodes is not None:
        os.environ["SIMSTEP_MAX_NODES"] = str(args.simstep_max_nodes)
    if args.simstep_budget_ms is not None:
        os.environ["SIMSTEP_BUDGET_MS"] = str(args.simstep_budget_ms)
    if args.simstep_budget64_ms is not None:
        os.environ["SIMSTEP_BUDGET64_MS"] = str(args.simstep_budget64_ms)
    if args.simstep_vmem_budget is not None:
        os.environ["SIMSTEP_VMEM_BUDGET"] = str(args.simstep_vmem_budget)
    if args.resume:
        os.environ["CAMPAIGN_RESUME"] = "1"
    if args.max_cells is not None:
        os.environ["CAMPAIGN_MAX_CELLS"] = str(args.max_cells)
    if args.certify_max_nodes is not None:
        os.environ["CERTIFY_MAX_NODES"] = str(args.certify_max_nodes)
    if args.certify_budget_ms is not None:
        os.environ["CERTIFY_BUDGET_MS"] = str(args.certify_budget_ms)
    if args.obs_budget_ratio is not None:
        os.environ["OBS_BUDGET_RATIO"] = str(args.obs_budget_ratio)
    if args.ml_traffic_max_workloads is not None:
        os.environ["ML_TRAFFIC_MAX_WORKLOADS"] = str(
            args.ml_traffic_max_workloads)
    if args.ml_traffic_budget_ms is not None:
        os.environ["ML_TRAFFIC_BUDGET_MS"] = str(
            args.ml_traffic_budget_ms)

    want = [ALIASES.get(s, s) for s in args.stages] or list(STAGES)
    unknown = sorted(set(want) - set(STAGES))
    if unknown:
        # fail fast, before any stage runs — a typo must not silently
        # skip work at the end of a long benchmark session
        raise SystemExit(
            f"unknown stage(s): {', '.join(unknown)}\n"
            f"valid stages: {', '.join(STAGES)} "
            f"(aliases: {', '.join(f'{a}->{b}' for a, b in ALIASES.items())})")

    t_all = time.time()
    records: list[dict] = []
    try:
        for name in want:
            print(f"\n================ {name} ================",
                  flush=True)
            t0 = time.time()
            try:
                ret = STAGES[name]()
            except BaseException as e:
                records.append({"stage": name, "ok": False,
                                "wall_s": round(time.time() - t0, 2),
                                "error": repr(e)})
                raise
            records.append({"stage": name, "ok": True,
                            "wall_s": round(time.time() - t0, 2),
                            "metrics": ret if isinstance(ret, dict)
                            else None})
            print(f"[{name} done in {time.time() - t0:.1f}s]",
                  flush=True)
        print(f"\nall benchmarks done in {time.time() - t_all:.1f}s")
    finally:
        if args.json is not None:
            import json as json_mod
            blob = json_mod.dumps(
                {"stages": records,
                 "total_wall_s": round(time.time() - t_all, 2),
                 "ok": all(r["ok"] for r in records)},
                indent=1, sort_keys=True)
            if args.json == "-":
                print(blob, flush=True)
            else:
                with open(args.json, "w") as f:
                    f.write(blob + "\n")


if __name__ == "__main__":
    main()
