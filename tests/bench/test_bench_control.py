"""The comparison fails what it must.

* The precision control: the reference put in the program's place in the
  nearest precision below the configuration's.  For the planner (fp32 on
  the chip) that is bfloat16, at the cell's own size on three seeds; for
  the simulator's generation tables (float32) also bfloat16.
* Faults planted in the timed path under a whole run at a tiny size: a
  step that returns its state unchanged, half of the lanes left out, an
  answer altered where it is produced, a replanned table altered, and,
  where lanes are sharded over chips, the gather of the other chips'
  lanes left out.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from bench_replans import replan_inputs
from bench_tiny import run_tiny, tiny_copy


@pytest.fixture(autouse=True)
def _no_cache_dir_change(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


@pytest.mark.parametrize("seed", [158735332, 5, 2**31 + 3])
def test_bf16_planner_fails_argmin_gap(seed):
    import jax.numpy as jnp

    from qsbench.check import LIMITS
    from qsbench.ref.planner import Planner, choice_gap

    grid, base, inputs = replan_inputs(seed, 2)
    p = Planner(grid)
    prev = p.plan(base)["w_final"]
    prev16 = p.plan(base, dtype=jnp.bfloat16)["w_final"]
    gaps = []
    for m, bw in inputs:
        ref = p.plan(m, bw=bw, w0=m.sum(1) + prev)
        low = p.plan(m, bw=bw, w0=m.sum(1) + prev16, dtype=jnp.bfloat16)
        prev, prev16 = ref["w_final"], low["w_final"]
        gaps.append(choice_gap(ref["costs"], low["choice"],
                               ref["unroutable"]))
    assert max(gaps) > 3 * LIMITS["argmin_gap"], gaps


def test_bf16_generation_tables_fail_lanes():
    import jax.numpy as jnp

    from qsbench.check import lanes_differing
    from qsbench.generator import pattern_matrix
    from qsbench.ref import sim as rsim
    from qsbench.ref.grid import make_grid

    grid = make_grid("mesh", (6, 6))
    tm = pattern_matrix(grid, "transpose")
    sim = dict(algo="XY", num_vcs=2, buf_per_vc=32, packet_len=4,
               src_queue_pkts=64, cycles=200, warmup=66, drain=0,
               lat_bins=96, lat_bin_width=8)
    pts = [(0.05, 1), (0.2, 2), (0.4, 3)]
    ref = rsim.run_campaign_lanes(grid, tm, None, sim, pts, 50)
    low = rsim.run_campaign_lanes(grid, tm, None, sim, pts, 50,
                                  gen_dtype=jnp.bfloat16)
    assert lanes_differing(low, ref) >= 1


def test_bf16_generation_tables_fail_uniform_lanes():
    """The same control on the four-chip cell's mix: XY, uniform, its
    eight rates."""
    import jax.numpy as jnp

    from qsbench.check import lanes_differing
    from qsbench.generator import pattern_matrix
    from qsbench.ref import sim as rsim
    from qsbench.ref.grid import make_grid

    grid = make_grid("mesh", (6, 6))
    tm = pattern_matrix(grid, "uniform")
    sim = dict(algo="XY", num_vcs=2, buf_per_vc=32, packet_len=4,
               src_queue_pkts=64, cycles=200, warmup=66, drain=0,
               lat_bins=96, lat_bin_width=8)
    pts = [(0.01 * (i + 1), 11 + i) for i in range(8)]
    ref = rsim.run_campaign_lanes(grid, tm, None, sim, pts, 50)
    low = rsim.run_campaign_lanes(grid, tm, None, sim, pts, 50,
                                  gen_dtype=jnp.bfloat16)
    assert lanes_differing(low, ref) >= 1


def _identity_runner(monkeypatch, module):
    def get_runner(*a, **kw):
        return lambda tables, state: state
    monkeypatch.setattr(module, "get_runner", get_runner)


def _half_runner(monkeypatch, module):
    import jax

    orig = module.get_runner

    def get_runner(meta, cfg, n, *, num_lanes=None, multi_device=None):
        half = max(1, num_lanes // 2)
        run = orig(meta, cfg, n, num_lanes=half, multi_device=False)

        def fn(tables, state):
            out = run(tables, jax.tree.map(lambda x: x[:half], state))
            rest = num_lanes - half
            return jax.tree.map(
                lambda a: np.concatenate([a, a[:rest]]) if a.ndim else a,
                out)
        return fn
    monkeypatch.setattr(module, "get_runner", get_runner)


def _altered_answer(monkeypatch, module):
    orig = module.postprocess

    def postprocess(o, cfg, topo, **kw):
        r = orig(o, cfg, topo, **kw)
        return dataclasses.replace(r, ejected_flits=r.ejected_flits + 1)
    monkeypatch.setattr(module, "postprocess", postprocess)


def _first_chip_only(monkeypatch, module):
    """The sharded runner's lanes gathered from the first chip alone:
    every other chip's shard replaced by the first one's."""
    import jax

    orig = module.get_runner

    def get_runner(meta, cfg, n, *, num_lanes=None, multi_device=None):
        run = orig(meta, cfg, n, num_lanes=num_lanes, multi_device=True)

        def first_chip(a):
            s0 = np.asarray(a.addressable_shards[0].data)
            return np.concatenate([s0] * (a.shape[0] // s0.shape[0]))

        return lambda tables, state: jax.tree.map(first_chip,
                                                  run(tables, state))
    monkeypatch.setattr(module, "get_runner", get_runner)


def _one_chip_wrong(chip: int):
    """The sharded runner with one chip's shard wrong, in the cell's
    layout of four chips: chip ``chip``'s block of lanes replaced by the
    first chip's."""
    def plant(monkeypatch, module):
        import jax

        orig = module.get_runner

        def get_runner(meta, cfg, n, *, num_lanes=None, multi_device=None):
            run = orig(meta, cfg, n, num_lanes=num_lanes, multi_device=True)

            def wrong(a):
                a = np.array(a)
                if a.ndim:
                    b = a.shape[0] // 4
                    a[chip * b:(chip + 1) * b] = a[:b]
                return a

            return lambda tables, state: jax.tree.map(wrong,
                                                      run(tables, state))
        monkeypatch.setattr(module, "get_runner", get_runner)
    return plant


FAULTS = {"state_unchanged": _identity_runner, "half_lanes": _half_runner,
          "answer_altered": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", ["mesh32-bidor-transpose",
                                      "torus16-chaos-online",
                                      "mesh32-xy-uniform-4chip"])
def test_planted_fault_fails(tmp_path, monkeypatch, fault, workload):
    from repro.noc import campaign, ctrl
    FAULTS[fault](monkeypatch,
                  campaign if workload.startswith("mesh") else ctrl)
    tiny_copy(str(tmp_path))
    res = run_tiny(str(tmp_path), workload, seconds=0.3)
    assert not res["correct"], res["checks"]


def test_altered_replan_table_fails(tmp_path, monkeypatch):
    from repro.noc import ctrl
    orig = ctrl.greedy_refine

    def greedy_refine(topo, traffic, table, **kw):
        out = orig(topo, traffic, table, **kw)
        choice = np.array(out.choice, copy=True)
        choice[0, 1] = 1 - choice[0, 1]
        return dataclasses.replace(out, choice=choice)
    monkeypatch.setattr(ctrl, "greedy_refine", greedy_refine)
    tiny_copy(str(tmp_path))
    res = run_tiny(str(tmp_path), "torus16-chaos-online", seconds=0.3)
    assert not res["correct"]
    assert res["checks"]["refine_entries_differing"]["value"] > 0


def test_exchange_left_out_fails(tmp_path, monkeypatch, multi_device_count):
    """The four-chip cell with the other chips' lanes left out of the
    gather (on the CPU's host devices, as many as the test run has)."""
    from repro.noc import campaign
    _first_chip_only(monkeypatch, campaign)
    tiny_copy(str(tmp_path))
    res = run_tiny(str(tmp_path), "mesh32-xy-uniform-4chip", seconds=0.3)
    assert not res["correct"]
    assert res["checks"]["lanes_differing"]["value"] > 0


@pytest.mark.parametrize("chip,seed", [(1, 7), (2, 2**31 + 5),
                                       (3, 123456791)])
def test_one_chip_shard_wrong_fails(tmp_path, monkeypatch,
                                    multi_device_count, chip, seed):
    """A fault in one chip's shard alone: the comparison samples a lane
    of every chip's block, so it fails on every seed."""
    from repro.noc import campaign
    _one_chip_wrong(chip)(monkeypatch, campaign)
    tiny_copy(str(tmp_path))
    res = run_tiny(str(tmp_path), "mesh32-xy-uniform-4chip", seed=seed,
                   seconds=0.3)
    assert not res["correct"]
    assert res["checks"]["lanes_differing"]["value"] > 0
