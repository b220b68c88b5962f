"""The readers of the per-layer metrics that the program's own spans
inside a replan and the campaign service give, on synthetic runs; each
reads nothing, and raises nothing, where the program has no such span."""

from __future__ import annotations

import pytest

from bench_tiny import BENCH  # noqa: F401  (puts bench/ on the path)


def _run(spans, jobs=()):
    from qsbench.harness import Run
    return Run(workload="w", config={}, mix={}, setup_s=1.0, window_s=10.0,
               jobs=list(jobs), spans=spans, trace=None, peaks={})


def _s(name, ts, dur_ms, **args):
    return dict(name=name, ts=float(ts), dur=dur_ms * 1e3, args=args)


def _read(name, run):
    from qsbench.harness import load_metric
    return load_metric(name).read(run)


# two replans, and the session's seed plan outside either
REPLANS = [
    _s("build_plan_fast", 0, 50, nodes=16),
    _s("plan_device", 10, 30, warm=False),
    _s("replan", 1e6, 1000, replan=0, cycle=1000),
    _s("plan_device", 1.01e6, 60, replan=0, warm=True),
    _s("greedy_refine", 1.1e6, 600, replan=0, pairs=20000, sweeps_run=2,
       changed=9),
    _s("hot_swap", 1.9e6, 20, replan=0, shed_pairs=0, rejected=False),
    _s("replan", 3e6, 2000, replan=1, cycle=2000),
    _s("plan_device", 3.01e6, 80, replan=1, warm=True),
    _s("greedy_refine", 3.1e6, 1500, replan=1, pairs=50000, sweeps_run=2,
       changed=4),
    _s("hot_swap", 4.9e6, 40, replan=1, shed_pairs=3, rejected=False),
]


@pytest.mark.parametrize("name,want", [
    ("greedy_refine_ms.replan", 1050.0),
    ("refine_us_per_pair.replan", 30.0),      # 600e3/20000, 1500e3/50000
    ("hot_swap_ms.replan", 30.0),
    ("plan_device_ms.replan", 70.0),          # the seed plan's left out
])
def test_replan_readers(name, want):
    assert _read(name, _run(REPLANS)) == pytest.approx(want)
    # a program without the span (the parent of these spans) reads none
    bare = [s for s in REPLANS if s["name"] in ("replan",
                                                "build_plan_fast")]
    assert _read(name, _run(bare)) is None


def test_refine_per_pair_skips_a_refine_without_pairs():
    spans = REPLANS + [_s("greedy_refine", 6e6, 5, replan=2, pairs=0)]
    assert _read("refine_us_per_pair.replan", _run(spans)) == \
        pytest.approx(30.0)


def test_service_self_time_per_job():
    jobs = [dict(cells_wall_s=[1.0, 1.0], wall_s=2.1),
            dict(cells_wall_s=[1.0, 1.0], wall_s=2.1)]
    spans = []
    for j, t in enumerate((0, 1e7)):
        spans += [_s("job", t, 2100, index=j),
                  _s("job_open", t, 5, job=f"j{j}"),
                  _s("prep_topo", t + 1e4, 10, slug="a", cached=True),
                  _s("cell", t + 2e4, 1000, slug="a"),
                  _s("chunk", t + 3e4, 200, slug="a", cycles=250),
                  _s("cell_save", t + 1.1e6, 7, slug="a"),
                  _s("prep_topo", t + 1.2e6, 1, slug="b", cached=True),
                  _s("cell", t + 1.3e6, 1000, slug="b"),
                  _s("cell_save", t + 2.4e6, 9, slug="b")]
    assert _read("service_self_ms_per_job", _run(spans, jobs)) == \
        pytest.approx(5 + 10 + 7 + 1 + 9)
    parent = [s for s in spans if s["name"] in ("job", "cell")]
    assert _read("service_self_ms_per_job", _run(parent, jobs)) is None
    assert _read("service_self_ms_per_job", _run(spans, [])) is None
