"""Shared helpers for the paper-experiment benchmarks.

Communication accounting follows paper Section 1.2: UpCom/DownCom are floats
per participating client per round; TotalCom = UpCom + alpha * DownCom.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Optional

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def totalcom(trace: dict, alpha: float) -> np.ndarray:
    return trace["up_floats"] + alpha * trace["down_floats"]


def floats_to_accuracy(trace: dict, target: float, alpha: float):
    """First TotalCom value at which suboptimality <= target (None if never)."""
    sub = trace["suboptimality"]
    idx = np.argmax(sub <= target)
    if sub[idx] > target:
        return None
    return float(totalcom(trace, alpha)[idx])


def summarize(traces: dict, target: float, alpha: float) -> dict:
    out = {}
    for name, tr in traces.items():
        out[name] = floats_to_accuracy(tr, target, alpha)
    return out


def run_cpu_child(argv, *, what: str, devices: int = 0,
                  smoke: Optional[bool] = None,
                  env_extra: Optional[dict] = None,
                  timeout: int = 1800) -> str:
    """Run one CPU bench child (``python <argv>``) from the repo root and
    return its stdout.  The child is pinned to ``JAX_PLATFORMS=cpu``: the
    parent has already touched JAX, so on a chip host it holds the chip
    and a child that reached for it would fail or hang.  ``devices``
    forces that many host devices (0: the single real CPU device);
    ``smoke`` sets or clears ``REPRO_BENCH_SMOKE`` (None leaves it).  A
    failed child raises ``RuntimeError`` with its stderr."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}" if devices
        else ""
    )
    if smoke is not None:
        if smoke:
            env["REPRO_BENCH_SMOKE"] = "1"
        else:
            env.pop("REPRO_BENCH_SMOKE", None)
    env.update(env_extra or {})
    env["PYTHONPATH"] = (
        os.path.join(REPO, "src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    proc = subprocess.run(
        [sys.executable, *argv], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=REPO,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"{what} child failed (rc {proc.returncode}):\n"
                           f"{proc.stderr}")
    return proc.stdout


def child_json(code: str, **kw):
    """``run_cpu_child`` of ``python -c code``; its last stdout line
    parsed as JSON."""
    return json.loads(run_cpu_child(["-c", code], **kw)
                      .strip().splitlines()[-1])
