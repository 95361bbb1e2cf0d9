"""``chip_smoke.py`` at a tiny size on the CPU: its phases pass, a recall
below the floor fails them, and ``main`` refuses to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

import chip_smoke

TINY = dict(num_base=1000, num_queries=16)


def test_one_chip_phases_pass_on_cpu():
    lines = []
    out = chip_smoke.run_one_chip(**TINY, recall_floor=0.9, platform="cpu",
                                  batch=16, log=lines.append)
    assert out["recall_batch_flush"] >= 0.9
    assert out["recall_continuous"] >= 0.9
    assert out["pallas_agreement"] >= chip_smoke.MIN_AGREEMENT
    assert any("not a benchmark number" in ln for ln in lines)


def test_one_chip_phases_fail_below_recall_floor():
    with pytest.raises(chip_smoke.SmokeError, match="below floor"):
        chip_smoke.run_one_chip(**TINY, recall_floor=1.01, platform="cpu",
                                batch=16, log=lambda _: None)


def test_one_chip_phases_fail_off_the_named_platform():
    with pytest.raises(chip_smoke.SmokeError, match="corpus arrays on"):
        chip_smoke.run_one_chip(**TINY, recall_floor=0.0, platform="tpu",
                                batch=16, log=lambda _: None)


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_exits_nonzero_without_a_tpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    for line in out.splitlines():
        assert not line.startswith("{"), "printed a result without a chip"


_FOUR = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {root!r})
import chip_smoke
out = chip_smoke.run_four_chips(num_base=1000, num_queries=16,
                                recall_floor=0.9, log=lambda _: None)
print(json.dumps(out))
"""


def test_four_chip_phase_on_virtual_cpu_devices():
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _FOUR.format(root=root)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["recall_nsp"] >= 0.9 and out["recall_fetch"] >= 0.9
