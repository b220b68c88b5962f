"""Golden-regression harness: a pinned 4×4-mesh campaign.

The simulator is refactored aggressively (batching, packed state, device
sharding); this test makes any behavioural drift loud.  Integer flit
counts must match exactly — they are deterministic functions of the
per-point PRNG streams, which are platform-stable (threefry).  Float
statistics get a small tolerance for summation-order differences.

To update after an INTENTIONAL behaviour change:
    PYTHONPATH=src python tests/goldens/regen.py
"""

import json
import os

import numpy as np
import pytest

# long campaign runs; CI's golden job (and tier-1) always run them
pytestmark = pytest.mark.slow

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "goldens",
                           "campaign_4x4.json")
CTRL_GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "goldens",
                                "ctrl_4x4.json")

INT_FIELDS = ("injected", "ejected", "in_flight", "reorder", "meas_cycles")
FLOAT_FIELDS = ("throughput", "avg_latency", "p50_latency", "p99_latency",
                "link_load_max", "lcv")


def _regen_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "golden_regen", os.path.join(os.path.dirname(GOLDEN_PATH),
                                     "regen.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN_PATH) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def computed():
    return _regen_module().compute_goldens()


@pytest.fixture(scope="module")
def ctrl_golden():
    with open(CTRL_GOLDEN_PATH) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ctrl_computed():
    return _regen_module().compute_ctrl_goldens()


def _compare(golden, computed):
    mismatches = []
    for key, want in golden["points"].items():
        got = computed["points"][key]
        for f in INT_FIELDS:
            if got[f] != want[f]:
                mismatches.append(f"{key}.{f}: {got[f]} != {want[f]}")
        for f in FLOAT_FIELDS:
            if not np.isclose(got[f], want[f], rtol=1e-5, atol=1e-6):
                mismatches.append(f"{key}.{f}: {got[f]} != {want[f]}")
    return mismatches


def test_golden_point_set_matches(golden, computed):
    assert set(computed["points"]) == set(golden["points"])


def test_golden_campaign_matches(golden, computed):
    mismatches = _compare(golden, computed)
    assert not mismatches, (
        "golden campaign drifted (intentional? regen with "
        "`PYTHONPATH=src python tests/goldens/regen.py`):\n  "
        + "\n  ".join(mismatches))


def test_golden_conservation(computed):
    """The pinned campaign itself satisfies flit conservation."""
    for key, pt in computed["points"].items():
        assert pt["injected"] == pt["ejected"] + pt["in_flight"], key
        assert pt["reorder"] == 0, key  # XY and BiDOR are in-order


def test_golden_prng_layout_leaves_process_default(computed):
    """The simulator pins the threefry layout the goldens were cut with
    for its own runner calls only; the process-wide flag is untouched."""
    import jax
    assert computed["points"]
    assert jax.config.jax_threefry_partitionable is True


def test_ctrl_golden_point_set_matches(ctrl_golden, ctrl_computed):
    assert set(ctrl_computed["points"]) == set(ctrl_golden["points"])


def test_ctrl_golden_campaign_matches(ctrl_golden, ctrl_computed):
    mismatches = _compare(ctrl_golden, ctrl_computed)
    assert not mismatches, (
        "fault-scenario golden drifted (intentional? regen with "
        "`PYTHONPATH=src python tests/goldens/regen.py`):\n  "
        + "\n  ".join(mismatches))


def test_ctrl_golden_online_beats_stale(ctrl_computed):
    """The pinned scenario reproduces the headline property: the online
    re-planner's time-resolved peak max link load stays below the stale
    plan's for every seed, at no delivered-throughput cost, and both
    policies conserve flits and stay in-order."""
    pts = ctrl_computed["points"]
    for key, pt in pts.items():
        assert pt["injected"] == pt["ejected"] + pt["in_flight"], key
        assert pt["reorder"] == 0, key
    for seed in (0, 1):
        stale = pts[f"linkfail_stale/BIDOR/r0.35/s{seed}"]
        online = pts[f"linkfail_online/BIDOR/r0.35/s{seed}"]
        assert online["link_load_max"] < stale["link_load_max"], seed
        assert online["throughput"] >= stale["throughput"] * 0.98, seed
