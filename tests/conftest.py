"""Shared fixtures. NOTE: no XLA_FLAGS here by design — tests must see the
single host device; multi-device tests spawn subprocesses with their own
flags (see helpers below)."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SRC = REPO / "src"


def run_subprocess_devices(code: str, n_devices: int = 8, timeout: int = 900) -> str:
    """Run `code` in a fresh python with n host devices; returns stdout."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = str(SRC)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    if out.returncode != 0:
        raise AssertionError(f"subprocess failed:\nSTDOUT:{out.stdout}\nSTDERR:{out.stderr[-4000:]}")
    return out.stdout


@pytest.fixture(scope="session")
def rng_key():
    import jax

    return jax.random.PRNGKey(0)
