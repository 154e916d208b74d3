"""End-to-end system tests: the real drivers, run as a user would run them."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def _run(args, devices=8, timeout=1200, xla_flags=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if xla_flags is not None:
        env["XLA_FLAGS"] = xla_flags
    else:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    proc = subprocess.run(
        [sys.executable] + args, capture_output=True, text=True,
        timeout=timeout, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, (
        f"cmd {args} failed\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    )
    return proc.stdout + proc.stderr


def test_train_driver_end_to_end(tmp_path):
    out = _run([
        "-m", "repro.launch.train", "--arch", "gemma2-2b", "--reduced",
        "--rounds", "6", "--seq-len", "48", "--per-client-batch", "2",
        "--data-parallel", "4", "--model-parallel", "2",
        "--log", str(tmp_path / "m.csv"),
        "--checkpoint-dir", str(tmp_path / "ckpt"), "--checkpoint-every", "3",
    ])
    assert "final loss" in out
    assert (tmp_path / "m.csv").exists()
    assert (tmp_path / "ckpt" / "step_6").exists()


def test_train_driver_block_rs_partial_participation(tmp_path):
    """The blocked uplink at c < n end to end (ISSUE 5 acceptance), with
    the client population decoupled from the mesh (--clients 8 on 4 data
    shards: 2 stacked client rows per shard) — the elastic engine trains
    only the cohort and the blocked bands lie over its slots."""
    out = _run([
        "-m", "repro.launch.train", "--arch", "gemma2-2b", "--reduced",
        "--rounds", "4", "--seq-len", "32", "--per-client-batch", "1",
        "--data-parallel", "4", "--model-parallel", "1",
        "--clients", "8", "--cohort", "4", "--uplink", "block_rs",
        "--log", str(tmp_path / "m.csv"),
    ], devices=4)
    assert "final loss" in out
    assert (tmp_path / "m.csv").exists()


def test_train_driver_no_fuse_elastic(tmp_path):
    """The per-step escape hatch under the elastic gate: the gathered
    compact state shares no buffers with the donated step in a way that
    deletes the full state's scalars (regression — the first cut crashed
    comm_step with 'Array has been deleted')."""
    out = _run([
        "-m", "repro.launch.train", "--arch", "gemma2-2b", "--reduced",
        "--rounds", "2", "--seq-len", "32", "--per-client-batch", "1",
        "--data-parallel", "1", "--model-parallel", "1",
        "--clients", "4", "--cohort", "2", "--no-fuse",
    ], devices=1)
    assert "final loss" in out


def test_train_driver_profile_dir(tmp_path):
    """``--profile-dir`` at one bucket length: ``setup`` holds the fresh
    carry and the two warm rounds (the program after a fresh carry and
    after itself), the program's trace, lowering and compile under
    ``tamuna.compile.1``; ``rounds`` holds the later rounds and nothing
    lowered or compiled.  Round 2's draw switches the traces, so its
    ``tamuna.round`` span is in neither."""
    import collections
    import glob

    from jax.profiler import ProfileData

    from repro import spans
    from repro.launch import train

    # each program after each, in k*k + 1 rounds for k bucket lengths
    assert train._warm_lengths(1) == [1, 1]
    assert train._warm_lengths(2) == [1, 2, 2, 1, 1]
    assert len(train._warm_lengths(8)) == 17
    prof = tmp_path / "prof"
    out = _run([
        "-m", "repro.launch.train", "--arch", "gemma2-2b", "--reduced",
        "--rounds", "6", "--seq-len", "8", "--per-client-batch", "1",
        "--data-parallel", "1", "--model-parallel", "1",
        "--clients", "2", "--cohort", "2", "--max-L", "1",
        "--flush-every", "2", "--profile-dir", str(prof),
    ], devices=1)
    assert "final loss" in out

    def host_events(phase):
        path, = glob.glob(str(prof / phase / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
        return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for plane in ProfileData.from_file(path).planes
                if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events]

    def span_counts(events):
        return collections.Counter(n for n, _, _ in events
                                   if n.startswith(spans.PREFIX))

    warm, later = host_events("setup"), host_events("rounds")
    assert span_counts(warm) == {
        spans.INIT_CARRY: 1, spans.ROUND: 2, spans.DRAIN: 1, spans.FLUSH: 1,
        f"{spans.COMPILE}1": 1}
    lowering = "lower_sharding_computation"  # JAX's own annotation
    (s, e), = [(s, e) for n, s, e in warm if n == f"{spans.COMPILE}1"]
    assert any(n == lowering and s <= a <= b <= e for n, a, b in warm)
    assert span_counts(later) == {spans.ROUND: 3, spans.DRAIN: 2,
                                  spans.FLUSH: 2}
    assert not any(n == lowering for n, _, _ in later)


def test_serve_driver_end_to_end():
    out = _run([
        "-m", "repro.launch.serve", "--arch", "rwkv6-7b", "--reduced",
        "--batch", "2", "--prompt-len", "8", "--gen-len", "4",
        "--data-parallel", "2", "--model-parallel", "2",
    ], devices=4)
    assert "sample continuations" in out


@pytest.mark.slow
def test_dryrun_single_pair_production_mesh(tmp_path):
    """One real production-mesh dry-run (512 host devices) as a gate; the
    full 40x2 sweep runs via `python -m repro.launch.dryrun --all`."""
    out = _run(
        ["-m", "repro.launch.dryrun", "--arch", "whisper-tiny",
         "--shape", "decode_32k", "--out-dir", str(tmp_path)],
        xla_flags="",  # dryrun sets its own device count
        timeout=1800,
    )
    assert "all combinations lowered + compiled OK" in out
    rec = json.load(open(
        tmp_path / "pod16x16" / "whisper-tiny" / "decode_32k" / "decode.json"
    ))
    assert rec["roofline"]["dominant"] in ("compute", "memory", "collective")
    assert rec["cost_analysis"]["flops"] > 0
