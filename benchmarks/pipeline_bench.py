"""Pipelined-rounds benchmark: simulated straggler wall-clock vs staleness.

Drives the split-phase round engine (``rounds.make_pipelined_round_fn`` +
``run_rounds_pipelined``, DESIGN.md §14) on the tiny dense model at n=8
stacked clients, c=2, with the simulated clock priced by the MEASURED
straggler-tail distribution exported by ``examples/availability_sim.py
--dist --dist-out`` (per-step latency draws of its lognormal + 10x
straggler mixture, bootstrapped per round through
``faults.EmpiricalDelays``) — not a parametric stand-in.  The sweep:

  sync      τ=0, wait_all — the bulk-synchronous baseline: every round
            pays its slowest cohort member (identical op sequence to
            ``run_rounds``, equivalence-tested in tests/test_pipeline.py).
            Run at three seeds to measure the convergence noise band.
  τ=1,2 wait_all   bounded staleness, no admission cut: every uplink is
            still aggregated, but a round's commit barrier is deferred τ
            rounds, so consecutive rounds' straggler waits overlap — the
            wall-clock win with a bit-identical per-round aggregation
            (only the ORDER local compute sees x_bar changes).
  τ=1,2 quorum=1   additionally cut at the first arrival: late uplinks
            are dropped (their coordinates untouched) — the aggressive
            end of the staleness/quality trade.

Headline: ``speedup_at_tail`` = sync clock / best wait_all τ>=1 clock
among the τ whose final loss stays inside the sync seed band (widened by
one band-width) — the deepest staleness that costs no convergence.
Acceptance: >= 1.5x.  Also records per-scenario admitted /
late-dropped / uncovered-coordinate totals — the quality signals the
staleness sweep in EXPERIMENTS.md §Perf 10 discusses.

Writes ``BENCH_pipeline.json``.  ``run(smoke=True)`` (or
``REPRO_BENCH_SMOKE=1``) shrinks rounds/taus, writes the latency
distribution to a temp path, and skips all artifact writes.
"""

from __future__ import annotations

import json
import os
import tempfile

from benchmarks.common import child_json, run_cpu_child

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
ARTIFACT = os.path.join(REPO, "BENCH_pipeline.json")
LATENCY_DIST = os.path.join(HERE, "artifacts", "latency_dist.json")

_CODE = r"""
import json, os
import numpy as np
import jax, jax.numpy as jnp

from repro.models.transformer import ModelConfig
from repro.data import DataConfig, device_sampler
from repro.data.pipeline import SyntheticTokenPipeline
from repro.dist import faults, rounds, tamuna_dp

SMOKE = os.environ.get("REPRO_BENCH_SMOKE") == "1"
DIST = os.environ["REPRO_LATENCY_DIST"]
N, C, S = 8, 2, 2
ROUNDS = 6 if SMOKE else 40
TAUS = (1,) if SMOKE else (1, 2)
SYNC_SEEDS = (0,) if SMOKE else (0, 1, 2)

mesh = jax.make_mesh((1, 1), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = ModelConfig(family="dense", n_layers=2, d_model=32 if SMOKE else 64,
                  n_heads=2 if SMOKE else 4, n_kv_heads=2,
                  d_ff=64 if SMOKE else 128, vocab=128,
                  dtype=jnp.float32, remat=False)
dcfg = DataConfig(seq_len=16, per_client_batch=2, vocab=128, seed=0,
                  n_clients=N)
pipe = SyntheticTokenPipeline(dcfg, cfg, mesh)
data = pipe.device_data()
sampler = device_sampler(dcfg, cfg, mesh)
tcfg = tamuna_dp.DistTamunaConfig(gamma=0.05, c=C, s=S, p=0.5,
                                  uplink="masked_psum")
lat = faults.EmpiricalDelays.from_json(DIST, n=N, seed=0)
engine = rounds.make_pipelined_round_fn(cfg, tcfg, mesh,
                                        sample_batch=sampler, max_L=8,
                                        n=N, elastic=True)


class RowLogger:
    def __init__(self):
        self.rows = []

    def log(self, step, m):
        self.rows.append(dict(m))


def run_one(tau, policy, quorum=None, seed=0):
    st = tamuna_dp.init_state(jax.random.key(seed), cfg, mesh, tcfg, n=N)
    logger = RowLogger()
    st, last = rounds.run_rounds_pipelined(
        st, round_fn=engine, data=data, key=jax.random.key(seed + 10),
        rounds=ROUNDS, rng=np.random.default_rng(seed), p=tcfg.p,
        staleness=tau, flush_every=10, logger=logger, latency=lat,
        policy=policy, quorum=quorum,
    )
    rows = logger.rows
    return {
        "tau": tau, "policy": policy, "quorum": quorum, "seed": seed,
        "clock_s": float(last["commit_s"]),
        "loss": float(last["loss"]),
        "admitted_total": int(sum(r.get("admitted", C) for r in rows)),
        "late_dropped_total": int(sum(r.get("late_dropped", 0)
                                      for r in rows)),
        "uncovered_total": int(sum(r.get("uncovered", 0) for r in rows)),
        "local_steps": int(last["local_steps"]),
    }


sync_runs = [run_one(0, "wait_all", seed=s) for s in SYNC_SEEDS]
sync = sync_runs[0]
scenarios = [sync]
for tau in TAUS:
    scenarios.append(run_one(tau, "wait_all"))
for tau in TAUS:
    scenarios.append(run_one(tau, "quorum", quorum=1))
for r in scenarios:
    print(f"# tau={r['tau']} {r['policy']}"
          f"{'' if r['quorum'] is None else r['quorum']}: "
          f"clock {r['clock_s']:.1f}s loss {r['loss']:.4f} "
          f"late_dropped {r['late_dropped_total']}", flush=True)

losses = [r["loss"] for r in sync_runs]
band = max(losses) - min(losses)


def within(loss):
    # inside the sync seed band widened by one band-width on each side
    return min(losses) - band <= loss <= max(losses) + band


# headline: the deepest wait_all tau whose final loss stays within the
# sync noise band — the wall-clock win that costs no admission drops and
# no convergence (staleness is the only knob turned)
candidates = [r for r in scenarios if r["tau"] >= 1
              and r["policy"] == "wait_all" and within(r["loss"])]
best = (max(candidates, key=lambda r: sync["clock_s"] / r["clock_s"])
        if candidates else
        next(r for r in scenarios if r["tau"] == TAUS[0]
             and r["policy"] == "wait_all"))
speedup = sync["clock_s"] / max(best["clock_s"], 1e-12)
converged = within(best["loss"])
with open(DIST) as f:
    dist_meta = {k: v for k, v in json.load(f).items()
                 if not isinstance(v, list)}
out = {
    "rows": scenarios,
    "sync_seeds": sync_runs,
    "sync_loss_band": [min(losses), max(losses)],
    "speedup_at_tail": speedup,
    "speedup_tau": best["tau"],
    "tail_loss_within_sync_band": bool(converged),
    "per_tau_speedup": {str(r["tau"]): sync["clock_s"] / r["clock_s"]
                        for r in scenarios if r["policy"] == "wait_all"
                        and r["tau"] >= 1},
    "latency_dist": dist_meta,
    "acceptance": {"min_speedup_at_tail": 1.5,
                   "tail_within_sync_band": True},
    "config": {"n": N, "c": C, "s": S, "rounds": ROUNDS,
               "taus": list(TAUS), "uplink": tcfg.uplink,
               "p": tcfg.p, "max_L": 8, "arch": "dense",
               "d_model": cfg.d_model, "seq_len": dcfg.seq_len,
               "sync_seeds": list(SYNC_SEEDS)},
}
print(json.dumps(out))
"""


def _ensure_latency_dist(smoke: bool) -> str:
    """Run the availability example's --dist-out export (the measured
    straggler tail).  Smoke writes to a temp path — the checked-in
    artifact is never clobbered by a rot check."""
    if smoke:
        path = os.path.join(tempfile.mkdtemp(prefix="pipe_bench_"),
                            "latency_dist.json")
        rounds = 2
    else:
        path = LATENCY_DIST
        rounds = 12
    run_cpu_child(
        [os.path.join(REPO, "examples", "availability_sim.py"),
         "--dist", "--rounds", str(rounds), "--dist-out", path],
        what="latency-dist export",
    )
    return path


def _bench(smoke: bool, dist_path: str) -> dict:
    return child_json(_CODE, what="pipeline bench", smoke=smoke,
                      env_extra={"REPRO_LATENCY_DIST": dist_path})


def run(paper_scale: bool = False, smoke: bool = False,
        latency_dist: str = ""):
    """``latency_dist`` overrides the measured-distribution input (any
    availability_sim --dist-out export); by default the bench re-exports
    it so the clock is always priced at the current measured tail."""
    del paper_scale
    dist_path = latency_dist or _ensure_latency_dist(smoke=smoke)
    art = _bench(smoke=smoke, dist_path=dist_path)
    if not smoke:  # smoke runs must not clobber the measured artifact
        with open(ARTIFACT, "w") as f:
            json.dump(art, f, indent=1)
    rows = []
    for r in art["rows"]:
        pol = r["policy"] + ("" if r["quorum"] is None else str(r["quorum"]))
        tag = f"pipeline/n{art['config']['n']}/c{art['config']['c']}"
        rows.append({
            "name": f"{tag}/tau{r['tau']}/{pol}/clock_s",
            "us_per_call": round(r["clock_s"], 3),
            "derived": (f"loss={r['loss']:.4f},"
                        f"late_dropped={r['late_dropped_total']},"
                        f"uncovered={r['uncovered_total']}"),
        })
    rows.append({
        "name": "pipeline/speedup_at_tail",
        "us_per_call": round(art["speedup_at_tail"], 3),
        "derived": (f"acceptance: >= 1.5 with loss in sync band; "
                    f"tau={art['speedup_tau']}, "
                    f"band={art['sync_loss_band']}, "
                    f"within={art['tail_loss_within_sync_band']}"),
    })
    return rows


if __name__ == "__main__":
    for r in run(smoke=os.environ.get("REPRO_BENCH_SMOKE") == "1"):
        print(r)
