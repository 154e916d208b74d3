"""Round-engine benchmark: rounds must cost O(cohort), not O(population).

Two measurements, written to ``BENCH_dist_round.json`` at the repo root and
emitted as CSV rows via ``benchmarks/run.py``:

  ref_round    reference-core ``round_step`` wall time vs population size n
               at fixed cohort c, for the cohort-only gradient path
               (``FiniteSumProblem.grad_cohort``) against the seed's
               full-population scatter path (``grad_cohort=None`` fallback).
               The cohort path must stay ~flat in n (acceptance: n=512
               within 2x of n=16); the seed path grows ~linearly.

  dist_uplink  TAMUNA-DP comm-step wall time for the masked-psum uplink vs
               the blocked reduce-scatter-shaped uplink, on a forced
               8-device host mesh (spawned in a subprocess so this process
               keeps the single real CPU device, like the test suite does).
               Each uplink also gets a ``+fused_round_L4`` row timing one
               whole engine round (4 scanned local steps with on-device
               data + the comm step, donated; ``us_per_round``, not
               comparable to the comm-only rows).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

from benchmarks.common import child_json

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
ARTIFACT = os.path.join(REPO, "BENCH_dist_round.json")

REF_NS = (16, 64, 128, 512)
REF_C, REF_S, REF_D = 8, 4, 4096
ITERS = 60


def _bench_ref_round(n: int, cohort_path: bool) -> float:
    """us per round_step call, steady state, donated state buffers."""
    import jax

    from repro.core import problems, tamuna

    prob = problems.make_quadratic_problem(n=n, d=REF_D, kappa=100)
    if not cohort_path:
        # the seed path: scatter cohort models into (n, d), grad everything
        prob = dataclasses.replace(prob, grad_cohort=None)
    cfg = tamuna.TamunaConfig(
        gamma=2.0 / (prob.L + prob.mu), eta=0.1, p=0.2, c=REF_C, s=REF_S,
        geometric_L=False,  # fixed L = 5 local steps: deterministic work
    )
    step = jax.jit(
        lambda st, k: tamuna.round_step(prob, cfg, st, k),
        donate_argnums=(0,),
    )
    state = tamuna.init(prob)
    keys = jax.random.split(jax.random.key(0), ITERS + 10)
    for i in range(10):  # compile + warm caches
        state = step(state, keys[i])
    jax.block_until_ready(state.x_bar)
    t0 = time.perf_counter()
    for i in range(10, 10 + ITERS):
        state = step(state, keys[i])
    jax.block_until_ready(state.x_bar)
    return (time.perf_counter() - t0) / ITERS * 1e6


_DIST_CODE = r"""
import json, sys, time
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.transformer import ModelConfig
from repro.data import DataConfig, device_sampler
from repro.data.pipeline import SyntheticTokenPipeline
from repro.dist import rounds, sharding, tamuna_dp

mesh = jax.make_mesh((8, 1), ("data", "model"))
cfg = ModelConfig(family="dense", n_layers=2, d_model=128, n_heads=4,
                  n_kv_heads=2, d_ff=256, vocab=256, dtype=jnp.float32,
                  remat=False)
n = sharding.n_clients(mesh)
dcfg = DataConfig(seq_len=32, per_client_batch=2, vocab=cfg.vocab, seed=0)
pipe = SyntheticTokenPipeline(dcfg, cfg, mesh)
rows = []
for uplink in ("masked_psum", "block_rs"):
    tcfg = tamuna_dp.DistTamunaConfig(
        gamma=0.02, c=n, s=2, p=0.25, uplink=uplink)
    state = tamuna_dp.init_state(jax.random.key(0), cfg, mesh, tcfg)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                      tamuna_dp.state_pspecs(state, cfg, mesh),
                      is_leaf=lambda x: isinstance(x, P))
    state = jax.device_put(state, sh)
    comm = jax.jit(tamuna_dp.make_comm_step(cfg, tcfg, mesh))
    keys = [jax.random.key(i) for i in range(40)]
    for k in keys[:5]:
        state = comm(state, k)
    jax.block_until_ready(state.round)
    t0 = time.perf_counter()
    for k in keys[5:]:
        state = comm(state, k)
    jax.block_until_ready(state.round)
    us = (time.perf_counter() - t0) / 35 * 1e6
    d = sum(int(jnp.size(a)) // n for a in jax.tree.leaves(state.x))
    rows.append({"uplink": uplink, "us_per_comm": us, "n": n,
                 "s": tcfg.s, "d_per_client": d})
    # the same comm step inside the fused round engine program (L=4
    # scanned local steps with on-device data + comm, donated)
    fused = jax.jit(rounds.make_fused_round(
        cfg, tcfg, mesh, sample_batch=device_sampler(dcfg, cfg, mesh),
        L=4), donate_argnums=(0,))
    data = pipe.device_data()
    state = jax.device_put(
        tamuna_dp.init_state(jax.random.key(0), cfg, mesh, tcfg), sh)
    for i in range(3):
        state, _ = fused(state, jax.random.key_data(jax.random.key(i)),
                         data)
    jax.block_until_ready(state.round)
    t0 = time.perf_counter()
    for i in range(3, 13):
        state, _ = fused(state, jax.random.key_data(jax.random.key(i)),
                         data)
    jax.block_until_ready(state.round)
    rows.append({"uplink": uplink + "+fused_round_L4",
                 "us_per_round": (time.perf_counter() - t0) / 10 * 1e6,
                 "n": n, "s": tcfg.s, "d_per_client": d})
print(json.dumps(rows))
"""


def _bench_dist_uplink():
    return child_json(_DIST_CODE, what="dist_uplink bench", devices=8,
                      timeout=900)


def run(paper_scale: bool = False):
    del paper_scale
    rows = []
    ref = {"cohort": {}, "full_population": {}}
    for n in REF_NS:
        for mode, cohort in (("cohort", True), ("full_population", False)):
            us = _bench_ref_round(n, cohort)
            ref[mode][n] = us
            rows.append({
                "name": f"dist_round/ref_round/{mode}/n{n}",
                "us_per_call": us,
                "derived": f"c={REF_C},s={REF_S},d={REF_D},L=5",
            })
    ratio_cohort = ref["cohort"][512] / ref["cohort"][16]
    ratio_full = ref["full_population"][512] / ref["full_population"][16]
    rows.append({
        "name": "dist_round/ref_round/n512_over_n16(cohort)",
        "us_per_call": round(ratio_cohort, 3),
        "derived": "acceptance: <= 2.0 (round cost is O(c), not O(n))",
    })
    rows.append({
        "name": "dist_round/ref_round/n512_over_n16(full_population)",
        "us_per_call": round(ratio_full, 3),
        "derived": "seed path: grows ~linearly in n",
    })

    uplink = _bench_dist_uplink()
    for r in uplink:
        # comm-only rows time one comm step; fused rows time a whole
        # engine round (4 local fwd+bwd steps + comm) — different units,
        # keyed apart so the artifact is not read as a comm regression
        us = r.get("us_per_comm", r.get("us_per_round"))
        what = "round(L=4 local + comm)" if "us_per_round" in r else "comm"
        rows.append({
            "name": f"dist_round/dist_uplink/{r['uplink']}",
            "us_per_call": us,
            "derived": (f"{what},n={r['n']},s={r['s']},"
                        f"d_per_client={r['d_per_client']}"),
        })

    artifact = {
        "config": {"c": REF_C, "s": REF_S, "d": REF_D, "local_steps": 5,
                   "iters": ITERS, "populations": list(REF_NS)},
        "ref_round_us": ref,
        "ratio_n512_over_n16": {"cohort": ratio_cohort,
                                "full_population": ratio_full},
        "dist_uplink": uplink,
    }
    with open(ARTIFACT, "w") as f:
        json.dump(artifact, f, indent=1)
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
