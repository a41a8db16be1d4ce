"""Multi-device pipeline tests (subprocess: needs >1 host device).

The heavyweight numerical check lives in tests/pp_check.py; here we run it
for the paper-critical cases and check the gspmd_pp stacked pipeline.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _run(script_args, timeout=900):
    env = dict(os.environ)
    # CPU checks on virtual devices: a child must never reach for a chip
    # (the parent pytest process may hold it)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable] + script_args, cwd=ROOT,
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_pp_hybrid_and_gpipe_granite():
    out = _run(["tests/pp_check.py", "granite-8b", "gpipe,hybrid"])
    assert "OK" in out


@pytest.mark.slow
def test_pp_hybrid_rwkv():
    out = _run(["tests/pp_check.py", "rwkv6-1.6b", "hybrid"])
    assert "OK" in out


@pytest.mark.slow
def test_gspmd_pp_moe():
    out = _run(["tests/gpp_check.py", "grok-1-314b"])
    assert "OK" in out
