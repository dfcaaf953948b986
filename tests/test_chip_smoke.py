"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its
phases hold at toy size (the chip runs them at north-star size)."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("lone", [False, True])
def test_refuses_without_tpu(tmp_path, lone):
    """Nonzero exit and no result line on the CPU backend — in the
    checkout, and as a lone copy with nothing else of the repo."""
    cwd = REPO
    if lone:
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "needs a TPU" in res.stderr


@pytest.fixture
def fresh_metrics(monkeypatch):
    """chip_smoke reads the process-wide fault counters as a fresh
    process sees them; tests that ran earlier in this worker bumped them."""
    from open_simulator_tpu.telemetry import registry

    monkeypatch.setattr(registry, "REGISTRY", registry.MetricsRegistry())


def test_phases_at_toy_size(capsys, fresh_metrics):
    chip_smoke.run_phases(chip_smoke.TOY)
    out = capsys.readouterr().out
    for phase in ("plan", "placement", "serve", "faults"):
        assert f"chip_smoke {phase}: " in out


def test_mesh_at_toy_size(capsys):
    import jax

    chip_smoke.run_mesh(chip_smoke.TOY, jax.devices()[:4])
    out = capsys.readouterr().out
    assert out.count("chip_smoke mesh: ") == 1
    assert "chip_smoke mesh-2x2: " in out and "ROADMAP B3" in out

