"""Regenerate the golden campaign fixtures.

Usage:  PYTHONPATH=src python tests/goldens/regen.py
            [--out DIR] [--sim-path {blocked,fused,unfused}]

Writes ``campaign_4x4.json`` / ``ctrl_4x4.json`` next to this file — or
into ``--out DIR`` (e.g. in CI, which regenerates into a scratch dir and
uploads the diff against the committed fixtures as a workflow artifact).
``--sim-path`` selects the per-cycle transition (the fused flit-step
kernel, the default; the unfused oracle; or the blocked node-tile
kernel); CI regenerates with EACH and cross-diffs them, attesting the
bit-identity contract on the pinned fixtures themselves.
Overwrite the committed fixtures ONLY when a simulator change
intentionally alters behaviour, and say so in the commit message — the
golden test exists to make unintended changes loud.

The fixture pins integer flit counts exactly (they are deterministic
functions of the per-point PRNG stream) and float statistics to 6
significant digits.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "campaign_4x4.json")
CTRL_GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "ctrl_4x4.json")


# --sim-path choices: every per-cycle transition must regenerate the
# SAME fixtures (the fused kernel — whole-array or blocked over node
# tiles — is bit-identical to the unfused oracle), so CI regenerates
# with each and cross-diffs them.  Each entry maps the base SimConfig
# onto that path; "blocked" pins two 8-node tiles on the 4x4 mesh.
SIM_PATHS = {
    "fused": lambda cfg: cfg.replace(use_kernel=True),
    "unfused": lambda cfg: cfg.replace(use_kernel=False),
    "blocked": lambda cfg: cfg.replace(use_kernel=True,
                                       sim_tile_nodes=8),
}


def golden_spec(to_path=SIM_PATHS["fused"]):
    from repro.core import mesh2d
    from repro.noc import Algo, CampaignSpec, SimConfig

    return CampaignSpec(
        topo=mesh2d(4, 4),
        algos=(Algo.XY, Algo.BIDOR),
        patterns=("uniform", "tornado"),
        rates=(0.15, 0.5),
        seeds=(0, 1),
        base=to_path(SimConfig(cycles=1000, warmup=300, drain=100)),
    )


def ctrl_spec(to_path=SIM_PATHS["fused"]):
    """Pinned fault-scenario campaign: one central link retrains at 25%
    width mid-measure; the stale and online control policies face it."""
    from repro.core import mesh2d
    from repro.noc import (Algo, CampaignSpec, LinkFail, ReplanConfig,
                           Scenario, SimConfig)

    fail = (LinkFail(cycle=1200, links=((5, 6), (6, 5)), bw_scale=0.25),)
    rc = ReplanConfig(epoch=400)
    return CampaignSpec(
        topo=mesh2d(4, 4),
        algos=(Algo.BIDOR,),
        patterns=("uniform",),
        rates=(0.35,),
        seeds=(0, 1),
        base=to_path(SimConfig(cycles=2400, warmup=400)),
        scenarios=(
            Scenario("linkfail_stale", events=fail, policy="stale",
                     replan=rc),
            Scenario("linkfail_online", events=fail, policy="online",
                     replan=rc),
        ),
    )


def compute_goldens(to_path=SIM_PATHS["fused"]) -> dict:
    from repro.noc import run_campaign

    res = run_campaign(golden_spec(to_path))
    points = {}
    for p in res.points:
        r = p.result
        key = f"{p.pattern}/{p.algo.name}/r{p.rate}/s{p.seed}"
        points[key] = {
            "injected": r.injected_flits,
            "ejected": r.ejected_flits,
            "in_flight": r.in_flight_flits,
            "reorder": r.reorder_value,
            "meas_cycles": r.meas_cycles,
            "throughput": round(r.throughput, 6),
            "avg_latency": round(r.avg_latency, 6),
            "p50_latency": round(r.p50_latency, 6),
            "p99_latency": round(r.p99_latency, 6),
            "link_load_max": round(r.link_load_max, 6),
            "lcv": round(r.lcv, 6),
        }
    return {
        "description": "4x4-mesh golden campaign (see tests/goldens/"
                       "regen.py); pins simulator behaviour across "
                       "refactors",
        "points": points,
    }


def compute_ctrl_goldens(to_path=SIM_PATHS["fused"]) -> dict:
    from repro.noc import run_campaign

    res = run_campaign(ctrl_spec(to_path))
    points = {}
    for p in res.points:
        r = p.result
        key = f"{p.scenario}/{p.algo.name}/r{p.rate}/s{p.seed}"
        points[key] = {
            "injected": r.injected_flits,
            "ejected": r.ejected_flits,
            "in_flight": r.in_flight_flits,
            "reorder": r.reorder_value,
            "meas_cycles": r.meas_cycles,
            "throughput": round(r.throughput, 6),
            "avg_latency": round(r.avg_latency, 6),
            "p50_latency": round(r.p50_latency, 6),
            "p99_latency": round(r.p99_latency, 6),
            "link_load_max": round(r.link_load_max, 6),
            "lcv": round(r.lcv, 6),
        }
    return {
        "description": "4x4-mesh fault-scenario campaign (one link "
                       "degraded to 25% width mid-measure; stale vs "
                       "online control policy; see tests/goldens/"
                       "regen.py); pins the control plane's event "
                       "application, hot swap and re-planning",
        "points": points,
    }


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, metavar="DIR",
                    help="write the fixtures into DIR instead of "
                         "overwriting the committed ones (CI diffing)")
    ap.add_argument("--sim-path", default="fused",
                    choices=sorted(SIM_PATHS),
                    help="per-cycle transition to regenerate with: the "
                         "fused kernel (default, the simulator "
                         "default), the unfused oracle, or the blocked "
                         "node-tile kernel — all must produce identical "
                         "fixtures, which CI attests by regenerating "
                         "with each and cross-diffing")
    args = ap.parse_args(argv)
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache(os.path.join(os.path.dirname(__file__), "..", ".."))
    to_path = SIM_PATHS[args.sim_path]
    golden_path, ctrl_path = GOLDEN_PATH, CTRL_GOLDEN_PATH
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        golden_path = os.path.join(args.out,
                                   os.path.basename(GOLDEN_PATH))
        ctrl_path = os.path.join(args.out,
                                 os.path.basename(CTRL_GOLDEN_PATH))
    goldens = compute_goldens(to_path)
    with open(golden_path, "w") as f:
        json.dump(goldens, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {len(goldens['points'])} golden points to "
          f"{golden_path} ({args.sim_path} sim path)")
    ctrl = compute_ctrl_goldens(to_path)
    with open(ctrl_path, "w") as f:
        json.dump(ctrl, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {len(ctrl['points'])} ctrl golden points to "
          f"{ctrl_path} ({args.sim_path} sim path)")


if __name__ == "__main__":
    main()
