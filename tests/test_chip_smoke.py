"""chip_smoke.py on the CPU: it refuses to run there, and its logic —
phases, checks, the cpuref prefix identity, the four-chip identity path —
runs end to end on the virtual CPU mesh with only the device check
stubbed (here, never in the script)."""

import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from kubernetes_tpu.models import speculative

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("argv", [
    ["--nodes", "200", "--pods", "256", "--batch", "64", "--seq-pods",
     "128", "--anti-pods", "128", "--ref-pods", "32",
     "--anti-ref-pods", "8"],
    ["--chips", "4", "--nodes", "200", "--pods", "256", "--batch", "64"],
], ids=["one-chip", "chips-4"])
def test_chip_smoke_logic_on_cpu_mesh(argv, monkeypatch, capsys):
    devs = jax.devices()
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda chips: {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    })
    # the chip's branch of the speculative engine: the packed program
    monkeypatch.setattr(speculative, "FORCE_PACKED_PATH", True)
    assert chip_smoke.main(argv) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": devs[0].device_kind, "count": 8}}
    phases = [ln for ln in lines if ln.startswith("chip_smoke phase")]
    assert len(phases) == (2 if "--chips" in argv else 3)
    if "--chips" in argv:
        assert any(ln.startswith("chip_smoke sharded:") for ln in lines)
