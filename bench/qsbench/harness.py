"""The benchmark harness: one run of one cell.

Everything that belongs to one configuration, one traffic mix or one
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``bench/configs/<config>.json`` — the deployment as it is run;
* ``bench/traffic/<mix>.json`` — the traffic mix, read by
  :mod:`.generator`;
* ``bench/metrics/<metric>.py`` — a reader with ``read(run)`` returning
  the metric's value, or None where the run holds nothing to read.

A run: find the chip; load; run the warm-up job (set-up ends there);
run jobs back to back until ``--seconds`` have passed, the last one to
its end; read the device's memory peak; compare a sample of the window's
outputs with the plain reference; print the metrics.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME_OK = frozenset("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
                    "0123456789_.-")


class BenchError(RuntimeError):
    """The run cannot produce a result."""


def _checked_name(name: str) -> str:
    if not name or not set(name) <= NAME_OK or name[0] in ".-":
        raise BenchError(f"bad name {name!r}")
    return name


def load_json(kind: str, name: str, bench: str = BENCH) -> dict:
    path = os.path.join(bench, kind, _checked_name(name) + ".json")
    if not os.path.exists(path):
        raise BenchError(f"no {kind[:-1] if kind.endswith('s') else kind} "
                         f"named {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def load_metric(name: str, bench: str = BENCH):
    """The reader module of a metric, by name."""
    path = os.path.join(bench, "metrics", _checked_name(name) + ".py")
    if not os.path.exists(path):
        raise BenchError(f"no metric reader named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(benchmark: dict, workload: str, bench: str = BENCH) -> dict:
    """The cell's entry, configuration, traffic mix and metrics."""
    ws = [w for w in benchmark["workloads"] if w["name"] == workload]
    if not ws:
        raise BenchError(f"no workload named {workload!r}")
    w = ws[0]
    cfgs = [c for c in benchmark["configs"] if c["name"] == w["config"]]
    if not cfgs:
        raise BenchError(f"no configuration named {w['config']!r}")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return dict(
        workload=w, config_entry=cfgs[0],
        config=load_json("configs", w["config"], bench),
        mix=load_json("traffic", w["traffic"], bench),
        end_to_end=[m for m in benchmark["end_to_end"] if applies(m)],
        per_layer=[m for m in benchmark["per_layer"] if applies(m)])


@dataclasses.dataclass
class Run:
    """What a run measured, as the metric readers see it."""

    workload: str
    config: dict
    mix: dict
    setup_s: float
    window_s: float
    jobs: list            # output records of the measured jobs
    spans: list           # host spans: name, ts, dur (µs), args
    trace: dict | None    # xtrace.reduce() of the traced window
    peaks: dict           # the chip's published peaks


def check_device(chips: int):
    """The chips of this run; refuses anything but enough TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"needs {chips} TPU chips; JAX found {len(devs)}")
    return devs[:chips]


def peaks_for(kind: str, bench: str = BENCH) -> dict:
    with open(os.path.join(bench, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise BenchError(f"no published peaks for device kind {kind!r}")
    return table["devices"][kind]


class CompileCounter:
    """Counts backend compilations while attached."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


def say(**fields):
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def sample_jobs(outputs, seed: int, count: int) -> list:
    """``count`` jobs of the window, drawn from the seed, for the
    comparison (every job of a cell has the same size)."""
    if not outputs:
        return []
    rng = np.random.default_rng([int(seed), 0xC4EC])
    order = rng.permutation(len(outputs))
    return [outputs[int(i)] for i in order[:count]]


def run_cell(args, *, chips_found=None, bench: str = BENCH,
             root: str = ROOT) -> dict:
    """One run; returns the result object of the last line.

    ``chips_found`` stands in for the chip check (the tests drive the
    rest of a run on the CPU with it)."""
    t_start = time.perf_counter()
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    c = cell(benchmark, args.workload, bench)
    chips = int(c["workload"]["chips"])
    devs = chips_found if chips_found is not None else check_device(chips)
    if os.path.join(root, "src") not in sys.path:
        sys.path.insert(0, os.path.join(root, "src"))
    import jax
    try:
        from repro.compile_cache import use_checkout_cache
    except ImportError as e:
        raise BenchError(f"the program is not in this checkout: {e}")

    from . import check, drivers, generator
    from .ref.grid import make_grid

    cache = use_checkout_cache(root)
    kind = devs[0].device_kind
    peaks = peaks_for(kind, bench) if chips_found is None else {}
    config, mix = c["config"], c["mix"]
    grid = make_grid(config["fabric"], config["dims"])
    say(workload=args.workload, device_kind=kind.replace(" ", "_"),
        devices=len(devs), compile_cache=cache)
    traced = bool(args.trace)
    spans = drivers.SpanLog(keep=None if traced else ("replan",))
    workdir = os.path.join(root, ".bench_run", args.workload)
    svc = drivers.Service(config, mix, workdir, spans, traced=traced)
    stream = generator.jobs(mix, config, grid, args.seed)
    warm = svc.run(next(stream))
    svc.outputs.clear()
    spans.spans.clear()
    setup_s = time.perf_counter() - t_start
    say(phase="setup", setup_s=setup_s, warmup_job_s=warm["wall_s"])

    session = None
    if traced:
        # host annotations only: tracing every Python call slows the
        # host path the window measures.  The trace stays in memory: a
        # window of many short device ops exports hundreds of MB, and
        # the export to disk takes minutes
        from jax._src.lib import _profiler
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        session = _profiler.ProfilerSession(opts)
    # the profiler covers the window's first ``trace_jobs`` jobs (the
    # mix's choice; all of them by default): a device trace holds a
    # limited number of op events, and a job's scan loops record one per
    # op and cycle.  Stopping it takes tens of seconds, which the
    # window's clock leaves out
    trace_jobs = mix.get("trace_jobs")
    xspace, paused = None, 0.0

    def stop_trace():
        nonlocal session, xspace, paused
        t_stop = time.perf_counter()
        xspace = session.stop()
        paused += time.perf_counter() - t_stop
        say(phase="trace_stop", stop_s=time.perf_counter() - t_stop,
            xspace_bytes=len(xspace), traced_jobs=len(svc.outputs))
        session = None

    with CompileCounter() as compiles:
        ann = jax.profiler.TraceAnnotation("bench_window")
        ann.__enter__()
        win_unix_ns = time.time_ns()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 - paused < args.seconds:
            svc.run(next(stream))
            if session is not None and len(svc.outputs) == trace_jobs:
                ann.__exit__(None, None, None)
                stop_trace()
        window_s = time.perf_counter() - t0 - paused
        if session is not None or not traced:
            ann.__exit__(None, None, None)
        if session is not None:
            stop_trace()
    outputs = svc.outputs
    say(phase="window", window_s=window_s, jobs=len(outputs),
        compiles_in_window=compiles.count,
        replans=sum(len(o.get("replans", ())) for o in outputs))
    replan_ms = [round(x["dur"] / 1e3, 3) for x in spans.spans
                 if x["name"] == "replan"]
    if replan_ms:
        say(replan_ms=json.dumps(replan_ms))

    # the program places its lanes itself (the campaign service shards
    # them over every visible chip where they divide), so the chips this
    # run used are those that held memory, of all that JAX shows
    visible = jax.devices() if chips_found is None else devs
    held = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in visible]
    memory_peak = max(held)
    used = sum(1 for b in held if b > 0) or len(devs)
    say(phase="devices", visible=len(visible), used=used, cell_chips=chips)

    reduced = None
    if xspace is not None:
        from . import xtrace
        t_red = time.perf_counter()
        reduced = xtrace.reduce(xtrace.from_bytes(xspace), win_unix_ns,
                                spans.spans)
        del xspace
        say(phase="trace", reduce_s=time.perf_counter() - t_red,
            busy_s=reduced["busy_s"],
            window_s=reduced["window_s"],
            by_device=json.dumps(reduced["by_device"]),
            modules=json.dumps(sorted(reduced["modules"].items(),
                                      key=lambda kv: -kv[1])[:12]),
            gaps_by_span=json.dumps(reduced["gap_by_span"]),
            gaps_at=json.dumps(reduced["gaps_at"]),
            last_op_s=reduced["last_op_s"],
            op_events=sum(reduced["op_counts"].values()))
    run = Run(workload=args.workload, config=config, mix=mix,
              setup_s=setup_s, window_s=window_s, jobs=list(outputs),
              spans=list(spans.spans), trace=reduced, peaks=peaks)

    # the comparison, once the program's device state is gone
    seed_plan = svc.seed_plan()
    del svc, stream
    gc.collect()
    t_cmp = time.perf_counter()
    numbers = compare(check, grid, config, mix, outputs, args.seed,
                      seed_plan, chips)
    say(phase="compare", compare_s=time.perf_counter() - t_cmp)
    correct, checks = check.verdict(numbers)

    names = c["per_layer"] if traced else c["end_to_end"]
    metrics = {}
    for m in names:
        v = load_metric(m["name"], bench).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": used, "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(outputs),
              "failed": check.jobs_failed(outputs), "metrics": metrics,
              "device": device}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result


def compare(check, grid, config, mix, outputs, seed, seed_plan,
            chips: int = 1) -> dict:
    """The numbers of the comparison with the plain reference."""
    picked = sample_jobs(outputs, seed, 1)
    if not picked:
        return dict(jobs_failed=1)
    if mix["service"] == "campaign":
        return check.campaign_numbers(grid, config, mix, outputs,
                                      picked[0], seed_plan, seed,
                                      chips=chips)
    out = check.session_numbers(grid, config, mix, picked[0]["job"],
                                picked[0])
    out.update(jobs_failed=check.jobs_failed(outputs),
               lanes_unsound=check.lanes_unsound(outputs))
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description="Q-StaR chip benchmark")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    # libtpu logs to a fixed /tmp path unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        result = run_cell(args)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} value={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0

