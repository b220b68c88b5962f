"""The comparison fails what it must.

* The precision control: the reference put in the program's place in the
  nearest precision below the configuration's.  For the planner (fp32 on
  the chip) that is bfloat16, at the cell's own size on three seeds; for
  the simulator's generation tables (float32) also bfloat16.
* Faults planted in the timed path under a whole run at a tiny size: a
  step that returns its state unchanged, half of the lanes left out, an
  answer altered where it is produced, a replanned table altered.  Lane
  sharding has no exchange between chips, so that fault has nothing to
  break here.
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


FAULTS = {"state_unchanged": _identity_runner, "half_lanes": _half_runner,
          "answer_altered": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", ["mesh32-bidor-transpose",
                                      "torus16-chaos-online"])
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
