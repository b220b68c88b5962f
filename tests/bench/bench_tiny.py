"""A copy of the benchmark at a tiny size, for driving whole runs on the
CPU: ``bench/`` and ``BENCHMARK.json`` copied under a temporary root,
with every configuration cut to a small fabric and short jobs."""

from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

TINY_CONFIG = {
    "mesh": dict(dims=[4, 4], cycles=120, warmup=40, chunk=40),
    "torus": dict(dims=[4, 4], cycles=400, warmup=60, epoch=100),
}
TINY_STORM = dict(start=50, horizon=350, flap_period=50, region_radius=0)


def tiny_copy(root: str) -> str:
    """Copy the benchmark under ``root`` at a tiny size; returns the
    copy's ``bench`` directory."""
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    cdir = os.path.join(root, "bench", "configs")
    for name in os.listdir(cdir):
        p = os.path.join(cdir, name)
        with open(p) as f:
            c = json.load(f)
        c.update(TINY_CONFIG[c["fabric"]])
        with open(p, "w") as f:
            json.dump(c, f)
    tdir = os.path.join(root, "bench", "traffic")
    for name in os.listdir(tdir):
        p = os.path.join(tdir, name)
        with open(p) as f:
            m = json.load(f)
        if m["service"] == "control_plane":
            m["storm"].update(TINY_STORM)
        with open(p, "w") as f:
            json.dump(m, f)
    return os.path.join(root, "bench")


def run_tiny(root: str, workload: str, seed: int = 7, seconds: float = 1.0,
             chips: int | None = None, trace: int = 0):
    """One run of a cell of the tiny copy under ``root`` on the CPU
    (the chip check stood in by the CPU devices)."""
    import jax
    from qsbench import harness

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    w = [x for x in b["workloads"] if x["name"] == workload][0]
    args = harness.parse(["--workload", workload, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", str(trace)])
    n = chips or w["chips"]
    return harness.run_cell(args, chips_found=jax.devices()[:n],
                            bench=os.path.join(root, "bench"), root=root)
