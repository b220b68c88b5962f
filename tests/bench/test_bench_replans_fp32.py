"""The replan comparison of ``torus16-chaos-online`` with the program's
planner steered to fp32 (its precision on a TPU) on the CPU, over a dozen
seeds and more, 158735332 among them: the first replans of each seed's
first measured storm.

Each replan goes through the program's own ``replan`` (device planner →
BiDOR-G → certifier) and is compared stage by stage with the float64
reference: the N-Rank weights, the BiDOR argmin (by its cost gap, so
that a near-tie broken the other way costs what it is worth), the
unroutable pairs, and BiDOR-G applied to the program's own BiDOR table.
``test_bench_replans_fp32_storm.py`` runs every replan of a storm.
"""

from __future__ import annotations

import pytest

from bench_replans import compare_replans, fp32_planner, summary  # noqa: F401

SEEDS = [158735332, 1, 2, 3, 17, 42, 31337, 2024, 77777, 1000003,
         987654321, 123456789, 2147483647]


@pytest.mark.parametrize("seed", SEEDS)
def test_replans_hold_at_fp32(fp32_planner, seed):  # noqa: F811
    from qsbench.check import LIMITS

    rows = compare_replans(seed, 3)
    print(summary(seed, rows))
    assert len(rows) == 3
    assert all(r["gap"] <= LIMITS["argmin_gap"] for r in rows), rows
    assert all(r["shed"] == 0 and r["refine"] == 0 for r in rows), rows
    assert not any(r["w_nr"] for r in rows), rows
