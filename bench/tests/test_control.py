"""The control fails: the reference computed with float8 matrix operands
(the precision below the configurations' bfloat16) reads over the cell's
limits on every seed, while the program's own first rounds read under
them.  Rehearsal sizes, on the CPU, through ``bench/calibrate.py``."""

import json
import os
import subprocess
import sys

import pytest

from bench import correct, harness
from bench.tests.conftest import ROOT

CELLS = ["whisper_tiny.stacked_long_rounds",
         "stablelm_3b_share.stacked_sync_rounds"]
SEEDS = ["101", "102", "103"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_program_passes(cell):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "calibrate.py"),
         "--rehearse", "--workload", cell, "--seeds", *SEEDS,
         "--control-seeds", *SEEDS],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    limits = harness.workload(cell, rehearse=True)["limits"]
    sides = {"program": [], "control": []}
    for r in lines:
        if "side" in r:
            sides[r["side"]].append(correct.judge(r, limits))
    assert sides["program"] == [True] * len(SEEDS), lines
    assert sides["control"] == [False] * len(SEEDS), lines
