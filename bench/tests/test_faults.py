"""The check that decides ``correct`` catches a broken timed path: each
fault a cell can have, planted in the program, turns ``correct`` false,
and the sound program passes.  Rehearsal sizes, on the CPU, each run in a
process of its own."""

import json
import os
import subprocess
import sys

import pytest

from bench.tests.conftest import ROOT

CELLS = {
    "whisper_tiny.stacked_long_rounds": ("unchanged", "half_batch", "token"),
    "stablelm_3b_share.stacked_sync_rounds": ("unchanged", "half_batch",
                                              "token"),
}
CASES = [(cell, f) for cell, fs in CELLS.items() for f in ("none",) + fs]


def run(fault, cell):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.faulty", fault, cell],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_turns_correct_false(cell, fault):
    out = run(fault, cell)
    assert out["correct"] is (fault == "none"), out["checks"]
