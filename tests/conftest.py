"""Shared fixtures.  NOTE: no XLA device-count forcing in THIS process —
smoke tests and benches must see the real single CPU device; multi-device
tests run through the ``subproc`` fixture, which is where the
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` default lives.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

DEFAULT_DEVICES = int(os.environ.get("REPRO_TEST_DEVICES", "8"))


def run_in_subprocess(code: str, devices: int = DEFAULT_DEVICES,
                      timeout: int = 900):
    """Run python code in a fresh process with a forced host device count."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{proc.stdout}\n"
            f"STDERR:\n{proc.stderr}"
        )
    return proc.stdout


@pytest.fixture(scope="session")
def subproc():
    return run_in_subprocess
