"""The benchmark harness: its contract with BENCHMARK.json, discovery by
name, the chip check, and each cell driven end to end at a tiny size on
the CPU (the chip check stood in for by the CPU devices)."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

from bench_tiny import BENCH, REPO, run_tiny, tiny_copy

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(autouse=True)
def _no_cache_dir_change(monkeypatch, tmp_path):
    # the harness places JAX's compile cache unless the variable is set
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def _benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_names_files_and_metrics():
    b = _benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"]
    assert b["paths"] == ["bench", "tests/bench"]
    assert 1 <= b["run_seconds"] <= 51
    names = [c["name"] for c in b["configs"]]
    for c in b["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert os.path.exists(os.path.join(REPO, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
    cells = {w["name"]: w for w in b["workloads"]}
    assert len(cells) == len(b["workloads"])
    assert sum(w["chips"] == 4 for w in cells.values()) <= len(cells) // 2
    for w in cells.values():
        assert w["config"] in names and w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert len(w["why"]) <= 200
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
    for w in cells:
        def reports(ms):
            return [m["name"] for m in ms
                    if w in m.get("workloads", cells)]
        got = reports(b["end_to_end"])
        assert "setup_s" in got and len(got) >= 2
        assert reports(b["per_layer"])


def test_unknown_names_fail():
    from qsbench import harness
    b = _benchmark()
    with pytest.raises(harness.BenchError):
        harness.cell(b, "no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.load_json("configs", "no-such-config")
    with pytest.raises(harness.BenchError):
        harness.load_json("traffic", "no-such-mix")
    with pytest.raises(harness.BenchError):
        harness.load_metric("no_such_metric")
    with pytest.raises(harness.BenchError):
        harness.load_json("configs", "../BENCHMARK")


def _add_cell(root: str, base: str, config: dict, mix: dict) -> str:
    """Add, as new files and new entries, a configuration (``base``'s
    file with ``config`` laid over it), a traffic mix, a metric and a
    cell to the tiny copy under ``root``; returns the cell's name."""
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", base + ".json")) as f:
        cfg = json.load(f)
    cfg.update(config)
    name = cfg["name"]
    with open(os.path.join(bench, "configs", name + ".json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", name + "-mix.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "metrics", "jobs_per_s.py"), "w") as f:
        f.write("def read(run):\n    return len(run.jobs) / run.window_s\n")
    p = os.path.join(root, "BENCHMARK.json")
    with open(p) as f:
        b = json.load(f)
    b["configs"].append({"name": name, "source": "test",
                         "file": f"bench/configs/{name}.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": name + "-cell", "config": name,
                           "traffic": name + "-mix", "chips": 1,
                           "why": "test"})
    b["end_to_end"].append({"name": "jobs_per_s", "unit": "1/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": [name + "-cell"]})
    with open(p, "w") as f:
        json.dump(b, f)
    return name + "-cell"


def test_new_files_only_add_a_cell(tmp_path):
    """A configuration, a traffic mix, a metric and a cell added as new
    files and a new workloads entry, with no existing file edited."""
    tiny_copy(str(tmp_path))
    cell = _add_cell(str(tmp_path), "epiphany-v-mesh32",
                     dict(name="mesh5-new", dims=[5, 5]),
                     {"service": "campaign", "algo": "XY",
                      "pattern": "transpose", "rates": [0.05],
                      "seeds_per_rate": 2})
    res = run_tiny(str(tmp_path), cell, seconds=0.5)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"jobs_per_s", "setup_s"}


@pytest.mark.parametrize("service", ["campaign", "control_plane"])
def test_new_files_only_add_a_3d_torus_cell(tmp_path, service):
    """A 4x4x4 torus, the shape of a TPU v4 cube, added by new files
    alone: BiDOR campaign jobs (planner, plan cache, 7-port step) and
    control-plane sessions under a storm (replans, BiDOR-G and the
    certifier on three dateline dimensions), each correct against the
    reference."""
    tiny_copy(str(tmp_path))
    if service == "campaign":
        mix = {"service": "campaign", "algo": "BIDOR",
               "pattern": "transpose", "rates": [0.05, 0.2],
               "seeds_per_rate": 1}
        config = dict(cycles=120, warmup=40, chunk=40)
    else:
        with open(os.path.join(str(tmp_path), "bench", "traffic",
                               "chaos-online.json")) as f:
            mix = json.load(f)
        config = {}
    config.update(name="torus4x4x4-new", dims=[4, 4, 4])
    cell = _add_cell(str(tmp_path), "tpu-v5e-pod-torus16", config, mix)
    res = run_tiny(str(tmp_path), cell, seed=2**31 + 17, seconds=0.5)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"jobs_per_s", "setup_s"}
    if service == "control_plane":
        assert res["checks"]["schedule_differing"]["value"] == 0


@pytest.mark.parametrize("workload", ["mesh32-bidor-transpose",
                                      "torus16-chaos-online",
                                      "mesh32-xy-uniform-4chip"])
def test_cell_runs_correct_at_tiny_size(tmp_path, workload):
    tiny_copy(str(tmp_path))
    res = run_tiny(str(tmp_path), workload, seed=2**31 + 11,
                   seconds=1.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    b = _benchmark()
    want = {m["name"] for m in b["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["count"] >= 1


@pytest.mark.parametrize("seed", [7, 158735332, 2**31 + 3])
def test_sampled_lanes_cover_every_chip(seed):
    """The reference re-runs every lane of a job of up to four; of more,
    one lane of each chip's contiguous block where the lanes divide over
    the chips, and the plain draw otherwise."""
    import numpy as np

    from qsbench.check import MAX_LANES, sample_lanes

    assert sample_lanes(4, 1, seed) == [0, 1, 2, 3]
    assert sample_lanes(4, 4, seed) == [0, 1, 2, 3]
    assert sample_lanes(2, 1, seed) == [0, 1]
    for n in (16, 32):
        got = sample_lanes(n, 4, seed)
        assert [i // (n // 4) for i in got] == [0, 1, 2, 3]
    rng = np.random.default_rng([seed, 0x1A4E])
    plain = sorted(rng.permutation(16)[:MAX_LANES].tolist())
    assert sample_lanes(16, 1, seed) == plain
    assert sample_lanes(18, 4, seed) == sorted(
        np.random.default_rng([seed, 0x1A4E]).permutation(18)[:4].tolist())
    seen = {tuple(sample_lanes(16, 4, s)) for s in range(40)}
    assert len(seen) > 1


def test_refuses_without_a_chip(tmp_path):
    """Under JAX_PLATFORMS=cpu the command exits non-zero and prints no
    result, here and in a directory that holds only BENCHMARK.json and
    the benchmark's own files."""
    import shutil
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), alone)
    shutil.copytree(BENCH, alone / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    for cwd in (REPO, str(alone)):
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload",
             "mesh32-bidor-transpose", "--seed", "1", "--seconds", "1",
             "--trace", "0"], cwd=cwd, env=env, capture_output=True,
            text=True, timeout=300)
        assert p.returncode != 0
        assert "correct" not in p.stdout
        assert "needs a TPU" in p.stderr


def _storm_cell():
    from qsbench import harness
    from qsbench.ref.grid import make_grid
    c = harness.cell(_benchmark(), "torus16-chaos-online")
    config, mix = c["config"], c["mix"]
    return config, mix, make_grid(config["fabric"], config["dims"])


@pytest.mark.parametrize("seed", [158735332, 2**31 + 5])
def test_storm_follows_the_program_chaos_schedule(seed):
    """The mix's storm lands on the cycles and kinds of the program's own
    ``chaos_schedule`` with the same parameters, its flaps take as many
    links, and every session of a run meets the same chunk lengths as its
    warm-up."""
    import dataclasses

    from qsbench import generator, drivers
    from repro.noc import ChaosConfig, LinkFail, LinkRecover, chaos_schedule

    config, mix, grid = _storm_cell()
    fields = {f.name for f in dataclasses.fields(ChaosConfig)}
    cc = ChaosConfig(seed=seed, **{k: v for k, v in mix["storm"].items()
                                   if k in fields})
    assert set(mix["storm"]) <= fields
    prog = chaos_schedule(drivers.program_topology(config), cc).events

    def kind(e):
        return ("fail" if isinstance(e, LinkFail) else
                "recover" if isinstance(e, LinkRecover) else "drift")

    def links(e):      # a flap's link count; a region's depends on where
        n = len(getattr(e, "links", ()))
        return n if n == 2 * cc.flap_links else n > 0

    stream = generator.jobs(mix, config, grid, seed)
    warm = next(stream)
    for job in (warm, next(stream), next(stream)):
        ours = job["events"]
        assert [(e.cycle, e.kind, links(e)) for e in ours] == \
            [(e.cycle, kind(e), links(e)) for e in prog]
    assert any(e.traffic is not None for e in warm["events"])


@pytest.mark.parametrize("workload,traced_jobs", [
    ("torus16-chaos-online", "1"), ("mesh32-bidor-transpose", None)])
def test_traced_run_covers_the_mix_jobs(tmp_path, monkeypatch, capsys,
                                        workload, traced_jobs):
    """A traced run stops the profiler after the mix's ``trace_jobs``
    jobs, or at the window's end, and reports the per-layer metrics.
    The CPU trace has no device plane, so the reduction is stood in."""
    from qsbench import xtrace

    seen = {}

    def reduce(planes, window_unix_ns=None, host_spans=(), top=10):
        seen["host"] = [p.name for p in planes if p.name.startswith("/host:")]
        return dict(window_s=2.0, busy_s=1.0, devices=1, ops={}, modules={},
                    op_counts={}, by_device=[], device_ops=[], idle_gaps=[],
                    gap_by_span={}, gaps_at=[], last_op_s=1.9)

    monkeypatch.setattr(xtrace, "reduce", reduce)
    tiny_copy(str(tmp_path))
    res = run_tiny(str(tmp_path), workload, seed=2**31 + 13, seconds=1.0,
                   trace=1)
    out = capsys.readouterr().out
    stops = re.findall(r"phase=trace_stop .* traced_jobs=(\d+)", out)
    assert len(stops) == 1 and seen["host"]
    if traced_jobs is not None:
        assert stops == [traced_jobs]
    assert res["correct"], res["checks"]
    names = {m["name"] for m in _benchmark()["per_layer"]
             if workload in m["workloads"]}
    assert set(res["metrics"]) <= names
    assert res["device"]["busy_s"] == 1.0 and "breakdown" in res
