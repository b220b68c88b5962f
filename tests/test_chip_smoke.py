"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases pass end to end at a tiny size when the device check is steered
to the CPU here (the script itself has no such option)."""

import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(plan_sides=(4,), scale_side=4,
                        scale_rates=(0.1, 0.3), scale_cycles=300, big_side=6, big_cycles=200,
                        ctrl_side=4, ctrl_cycles=600, ctrl_epoch=200,
                        four_side=4, four_cycles=200)


def test_refuses_without_a_tpu(capsys):
    with pytest.raises(chip_smoke.SmokeFailure, match="needs a tpu"):
        chip_smoke.main([])
    assert capsys.readouterr().out == ""


def test_all_phases_at_tiny_size(capsys, monkeypatch, tmp_path):
    # JAX reads this variable only at import, so setting it here keeps
    # main() from placing a compile cache for the rest of the session
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    verdict = chip_smoke.main([], sizes=TINY, platform="cpu")
    assert verdict == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    out = capsys.readouterr().out.splitlines()
    assert out[-1].startswith('{"ok": true')
    assert "bidor_table_entries_differing=0" in "\n".join(out)


def test_four_chip_phase_on_host_devices():
    if jax.device_count() < 2:
        pytest.skip("needs the forced host device count (conftest.py)")
    chip_smoke.phase_four_chips(TINY)
