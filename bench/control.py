"""Readings behind the limits of ``correct``: the program's and the
precision control's, on many seeds of one cell, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

For each seed it runs the first measured job of that seed's stream
through the program (after one warm-up job), then prints one JSON line:
the numbers the comparison reads from the program, and the same numbers
for the control — the plain reference put in the program's place and
computed in the nearest precision below the configuration's (bfloat16
for the fp32 planner and the float32 generation tables).  The
benchmark's own runs never run the control.  Needs the cell's chips.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    from qsbench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        c = harness.cell(json.load(f), args.workload)
    try:
        devs = harness.check_device(int(c["workload"]["chips"]))
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    import jax.numpy as jnp
    from repro.compile_cache import use_checkout_cache

    from qsbench import check, drivers, generator
    from qsbench.ref.grid import make_grid

    use_checkout_cache(harness.ROOT)
    config, mix = c["config"], c["mix"]
    grid = make_grid(config["fabric"], config["dims"])
    low = jnp.bfloat16
    svc = drivers.Service(config, mix, os.path.join(
        harness.ROOT, ".bench_run", "control-" + args.workload),
        drivers.SpanLog(keep=()))
    seeds = [int(s) for s in args.seeds.split(",")]
    svc.run(next(generator.jobs(mix, config, grid, seeds[0])))
    for seed in seeds:
        stream = generator.jobs(mix, config, grid, seed)
        next(stream)
        svc.outputs.clear()
        out = svc.run(next(stream))
        if mix["service"] == "campaign":
            nums = check.campaign_numbers(grid, config, mix, [out], out,
                                          svc.seed_plan(), seed,
                                          control=low, chips=len(devs))
        else:
            nums = check.session_numbers(grid, config, mix, out["job"], out,
                                         control=low)
        print(json.dumps({"seed": seed, "kind": devs[0].device_kind,
                          "numbers": nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
