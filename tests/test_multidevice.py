"""Runs the device-count-dependent test modules in a subprocess with 8
forced host devices (the main pytest process must keep the real device
count — see conftest note), so `pytest tests/` covers them anyway."""

import os
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_sharding_suite_on_8_devices():
    env = dict(os.environ)
    # the child must never reach for an accelerator this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "pytest",
         os.path.join(os.path.dirname(__file__), "test_sharding.py"),
         "-q", "--no-header"],
        env=env, capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
