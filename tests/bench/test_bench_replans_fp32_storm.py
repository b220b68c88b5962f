"""Every replan of a whole storm of ``torus16-chaos-online`` with the
program's planner steered to fp32, the N-Rank fixed point carried from
each replan into the next: whether the carry widens the gap between the
fp32 planner and the float64 reference over a session.  Seed 158735332
is the seed on which a tighter entrywise limit once failed.

The replay with the simulation in the loop (the control plane's own
estimates, a dozen seeds) is ``replay_fp32_sessions.py``.
"""

from __future__ import annotations

import pytest

from bench_replans import (compare_replans, fp32_planner,  # noqa: F401
                           replan_inputs, summary)


@pytest.mark.parametrize("seed", [158735332])
def test_storm_replans_hold_at_fp32(fp32_planner, seed):  # noqa: F811
    from qsbench.check import LIMITS

    _, _, inputs = replan_inputs(seed)
    rows = compare_replans(seed)
    print(summary(seed, rows))
    assert len(rows) == len(inputs) >= 12
    assert all(r["gap"] <= LIMITS["argmin_gap"] for r in rows), rows
    assert all(r["shed"] == 0 and r["refine"] == 0 for r in rows), rows
    assert not any(r["w_nr"] for r in rows), rows
