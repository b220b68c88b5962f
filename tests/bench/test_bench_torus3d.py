"""The TPU v4 slice's 8x8x8 torus (``tpu-v4-torus8x8x8``) under the
online control plane: its configuration as the harness loads it, a
whole run of its cell on a 4x4x4 torus at a tiny size on the CPU, two
faults that only a 3-D fabric shows, and the reader of BiDOR-G's time
per route hop."""

from __future__ import annotations

import importlib
import json
import math
import os

import numpy as np
import pytest

from bench_tiny import REPO, run_tiny, tiny_copy

CELL = "torus8x8x8-chaos-online"
CONFIG = "tpu-v4-torus8x8x8"
SEED = 2**31 + 29


@pytest.fixture(autouse=True)
def _no_cache_dir_change(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def _benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_configuration_loads_at_its_full_size():
    from qsbench import harness
    from qsbench.ref.grid import make_grid

    c = harness.cell(_benchmark(), CELL)
    config = c["config"]
    assert c["workload"]["config"] == CONFIG == config["name"]
    assert c["workload"]["traffic"] == "chaos-online"
    assert c["workload"]["chips"] == 1
    assert c["config_entry"]["reduced"] == [] and config["reduced"] == {}
    assert config["fabric"] == "torus" and config["dims"] == [8, 8, 8]
    assert (config["cycles"], config["warmup"], config["epoch"]) == \
        (12000, 4000, 500)
    with open(os.path.join(REPO, "bench", "configs",
                           "tpu-v5e-pod-torus16.json")) as f:
        torus16 = json.load(f)
    for key in ("algo", "num_vcs", "buf_per_vc", "packet_len",
                "src_queue_pkts", "drain", "chunk", "replan",
                "planner_precision"):
        assert config[key] == torus16[key], key
    grid = make_grid(config["fabric"], config["dims"])
    assert grid.n == 512 == math.prod(config["dims"])
    assert len(grid.channels()) == 3072
    assert grid.orders == ((0, 1, 2), (2, 1, 0))
    assert {m["name"] for m in c["end_to_end"]} == \
        {"replan_ms_p50", "replan_ms_p60", "setup_s"}
    assert "refine_ns_per_hop.replan" in {m["name"] for m in c["per_layer"]}


def _tiny_torus(root: str, dims) -> None:
    """The tiny copy under ``root`` with the new configuration on a
    torus of ``dims`` (tiny_copy cuts every torus to 4x4)."""
    tiny_copy(root)
    p = os.path.join(root, "bench", "configs", CONFIG + ".json")
    with open(p) as f:
        config = json.load(f)
    config["dims"] = list(dims)
    with open(p, "w") as f:
        json.dump(config, f)


def test_cell_runs_correct_on_a_4x4x4_torus(tmp_path):
    """The cell's configuration, router and mix on a TPU v4 cube's
    4x4x4 torus: every check of the comparison at or under its limit,
    the exact ones at 0, with replans in the sampled session."""
    from qsbench.check import LIMITS

    _tiny_torus(str(tmp_path), (4, 4, 4))
    res = run_tiny(str(tmp_path), CELL, seed=SEED, seconds=0.5)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    checks = res["checks"]
    assert set(checks) == set(LIMITS)
    for name, c in checks.items():
        assert c["value"] <= c["limit"] == LIMITS[name], name
        if LIMITS[name] == 0:
            assert c["value"] == 0, name
    assert set(res["metrics"]) == {"replan_ms_p50", "replan_ms_p60",
                                   "setup_s"}


def _third_dimension_ports_swapped(monkeypatch):
    """The step's tables with the third dimension's + and − ports
    (4 and 5) swapped; a fabric of fewer dimensions has no such ports
    and keeps its tables."""
    import jax.numpy as jnp

    from repro.noc import ctrl

    orig = ctrl.build_tables

    def build_tables(topo, traffic, table, num_vcs):
        tables, meta = orig(topo, traffic, table, num_vcs)
        if topo.ndim >= 3:
            port = np.asarray(tables.port)
            port = np.where(port == 4, 5, np.where(port == 5, 4, port))
            tables = tables._replace(port=jnp.asarray(port))
        return tables, meta

    monkeypatch.setattr(ctrl, "build_tables", build_tables)
    return "lanes_differing"


def _descending_order_walks_xzy(monkeypatch):
    """BiDOR-G weighing the descending order ZYX along XZY routes; in
    2-D the descending order is YX and walks as it should."""
    # the module, which ``repro.core``'s function of the same name hides
    bidor = importlib.import_module("repro.core.bidor")
    orig = bidor.route_links

    def route_links(topo, order):
        return orig(topo, (0, 2, 1) if tuple(order) == (2, 1, 0)
                    else order)

    monkeypatch.setattr(bidor, "route_links", route_links)
    monkeypatch.setattr(bidor, "_ROUTES", {})
    return "refine_entries_differing"


FAULTS = {"third_dimension_ports_swapped": _third_dimension_ports_swapped,
          "descending_order_walks_xzy": _descending_order_walks_xzy}


@pytest.mark.parametrize("dims", [(4, 4, 4), (6, 6)])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_3d_fault_fails_only_in_3d(tmp_path, monkeypatch, fault, dims):
    """Each fault fails ``correct`` on the 4x4x4 torus, by its own
    check, and passes on the 6x6 torus: the comparison catches what is
    wrong in the third dimension alone."""
    check = FAULTS[fault](monkeypatch)
    _tiny_torus(str(tmp_path), dims)
    res = run_tiny(str(tmp_path), CELL, seed=SEED, seconds=0.3)
    if len(dims) == 3:
        assert not res["correct"]
        assert res["checks"][check]["value"] > 0, res["checks"]
    else:
        assert res["correct"], res["checks"]


def _run(spans):
    from qsbench.harness import Run
    return Run(workload="w", config={}, mix={}, setup_s=1.0, window_s=10.0,
               jobs=[], spans=spans, trace=None, peaks={})


def _refine(ts, dur_ms, **args):
    return dict(name="greedy_refine", ts=float(ts), dur=dur_ms * 1e3,
                args=args)


def test_refine_ns_per_hop_reads_the_median_over_replans():
    from qsbench.harness import load_metric

    read = load_metric("refine_ns_per_hop.replan").read
    spans = [_refine(0, 600, replan=0, pairs=20000, hops=300000),
             _refine(1e6, 1500, replan=1, pairs=50000, hops=500000),
             _refine(2e6, 900, replan=2, pairs=40000, hops=450000),
             _refine(3e6, 5, replan=3, pairs=0, hops=0)]
    # 2,000, 3,000 and 2,000 ns a hop; a refine with no hops is skipped
    assert read(_run(spans)) == pytest.approx(2000.0)
    # a program whose spans have no hops counter (the parent of the
    # counter) reads none
    bare = [_refine(0, 600, replan=0, pairs=20000),
            dict(name="replan", ts=0.0, dur=1e6, args={})]
    assert read(_run(bare)) is None
    assert read(_run([])) is None
