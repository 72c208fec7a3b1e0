"""``chip_smoke.py``: its phases at a tiny scale on the CPU, its reference
check, and its refusal to run without a TPU.

The script itself has no CPU path; these tests call its phase functions
directly (one in this process, the 4-device mesh phase in a subprocess
with four virtual CPU devices).
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
TINY = 1e-4                     # paper_matrix floors G7 at 256 x 256


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_one_chip_phase_passes_at_tiny_scale(smoke):
    smoke.serve_one_chip(scale=TINY)


def test_mesh_phase_passes_on_four_virtual_devices():
    code = ("import sys; sys.path.insert(0, sys.argv[1]); "
            "import chip_smoke; chip_smoke.serve_mesh(4, scale=float("
            "sys.argv[2])); print('mesh-ok')")
    res = subprocess.run(
        [sys.executable, "-c", code, str(ROOT), str(TINY)],
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    assert "mesh-ok" in res.stdout
    assert "[row x4]" in res.stdout and "[col x4]" in res.stdout


def test_reference_check_rejects_a_wrong_result(smoke):
    rows = np.array([0, 0, 1, 2])
    cols = np.array([0, 2, 1, 2])
    vals = np.array([1.0, 2.0, 3.0, -4.0], np.float32)
    ref = smoke.HostReference(rows, cols, vals, 3)
    x = np.array([1.0, -2.0, 0.5], np.float32)
    y = np.array([2.0, -6.0, -2.0], np.float32)
    ref.check(y, x, rtol=1e-4, what="exact")
    with pytest.raises(smoke.SmokeFailure, match="exceeds"):
        ref.check(y * (1 + 1e-3), x, rtol=1e-4, what="perturbed")
    with pytest.raises(smoke.SmokeFailure, match="non-finite"):
        ref.check(np.array([np.nan, -6.0, -2.0]), x, rtol=1e-4, what="nan")


def test_refuses_to_run_without_a_tpu():
    res = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=_env(), capture_output=True, text=True,
                         timeout=300, cwd=ROOT)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    lines = res.stdout.strip().splitlines()
    assert not lines or '"ok"' not in lines[-1]
    with pytest.raises((json.JSONDecodeError, IndexError)):
        json.loads(lines[-1])
