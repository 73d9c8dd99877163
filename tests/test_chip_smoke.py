"""``chip_smoke.py`` on the CPU: it refuses to report without a TPU or
outside a checkout, and its phases pass at a tiny size with the Pallas
kernels interpreted (the chip runs the same phases compiled, at the
paper's size)."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _run(script: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_refuses_cpu_and_names_platform():
    r = _run(ROOT / "chip_smoke.py")
    assert r.returncode != 0
    assert "found platform 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_refuses_outside_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run(tmp_path / "chip_smoke.py")
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_chip_smoke_phases_pass_at_tiny_size():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    assert cs.smoke(1, n_clients=10, scale=0.02, interpret=True) >= 0.0
