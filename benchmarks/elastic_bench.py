"""Elastic-round benchmark: cohort-gathered local compute vs all-rows.

Times ONE fused round (L=4 scanned local steps + comm, donated) of the
dist engine at n=16 stacked clients on a single device (the n-override
placement: the client axis is state rows, not mesh shards, so total
gradient work is what the wall clock sees), sweeping the cohort size
c in {n, n/2, n/4} for both uplinks:

  allrows  the pre-elastic engine (PR 4 behaviour): every round runs the
           L local steps on ALL n client rows regardless of c
           (``make_fused_round(..., elastic=False)``),
  gather   the elastic engine (DESIGN.md §11): gather the round's c
           cohort rows, run the L steps on the compact (c, ...) state
           with cohort-only batches, scatter back, comm — O(c·L) local
           compute, idle clients do nothing.

This is real compute reduction (fewer gradient FLOPs), not driver
overhead, so it benches on this 2-core box; the c = n row times the pure
gather/scatter overhead of the elastic path (expected ~1x: two extra
O(n·d) copies against L full fwd+bwd passes).

All variants are donated jits chaining their own output state,
interleaved min-of-reps (the box has multi-minute throughput phases).
Writes ``BENCH_elastic.json``; acceptance: gather >= 1.8x allrows at
n=16, c=n/4 on the WORST uplink, and never slower at any c < n.
``run(smoke=True)`` (or ``REPRO_BENCH_SMOKE=1``) shrinks to tiny shapes
and skips the artifact write — wired into tests/test_bench_tooling.py.
"""

from __future__ import annotations

import json
import os

from benchmarks.common import child_json

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
ARTIFACT = os.path.join(REPO, "BENCH_elastic.json")

_CODE = r"""
import json, os, time
import numpy as np
import jax, jax.numpy as jnp

from repro.models.transformer import ModelConfig
from repro.data import DataConfig, device_sampler
from repro.data.pipeline import SyntheticTokenPipeline
from repro.dist import rounds, tamuna_dp

SMOKE = os.environ.get("REPRO_BENCH_SMOKE") == "1"
N = 4 if SMOKE else 16
CS = (4, 2) if SMOKE else (16, 8, 4)
WARM, REPS = (1, 2) if SMOKE else (2, 10)
L, S = (2, 2) if SMOKE else (4, 2)

mesh = jax.make_mesh((1, 1), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = ModelConfig(family="dense", n_layers=2, d_model=64 if SMOKE else 128,
                  n_heads=4, n_kv_heads=2, d_ff=128 if SMOKE else 256,
                  vocab=256, dtype=jnp.float32, remat=False)
dcfg = DataConfig(seq_len=16 if SMOKE else 32, per_client_batch=2,
                  vocab=256, seed=0, n_clients=N)
pipe = SyntheticTokenPipeline(dcfg, cfg, mesh)
data = pipe.device_data()
sampler = device_sampler(dcfg, cfg, mesh)


def time_interleaved(fns, tcfg):
    states, ts = {}, {k: [] for k in fns}
    for k, fn in fns.items():
        st = tamuna_dp.init_state(jax.random.key(0), cfg, mesh, tcfg, n=N)
        for w in range(WARM):
            st, _ = fn(st, jax.random.key_data(jax.random.key(w)), data)
        jax.block_until_ready(st.round)
        states[k] = st
    for r in range(REPS):
        kd = jax.random.key_data(jax.random.key(100 + r))
        for k, fn in fns.items():
            t0 = time.perf_counter()
            states[k] = fn(states[k], kd, data)[0]
            jax.block_until_ready(states[k].round)
            ts[k].append(time.perf_counter() - t0)
    return {k: float(np.min(v)) * 1e6 for k, v in ts.items()}


rows = []
for uplink in ("masked_psum", "block_rs"):
    for c in CS:
        tcfg = tamuna_dp.DistTamunaConfig(gamma=0.05, c=c, s=min(S, c),
                                          p=1.0 / L, uplink=uplink)
        fns = {}
        for name, elastic in (("allrows", False), ("gather", True)):
            fns[name] = jax.jit(
                rounds.make_fused_round(cfg, tcfg, mesh,
                                        sample_batch=sampler, L=L, n=N,
                                        elastic=elastic),
                donate_argnums=(0,))
        timed = time_interleaved(fns, tcfg)
        row = {"n": N, "c": c, "s": tcfg.s, "L": L, "uplink": uplink,
               "allrows_us": timed["allrows"],
               "gather_us": timed["gather"],
               "speedup_gather_vs_allrows":
                   timed["allrows"] / timed["gather"]}
        rows.append(row)
        print(f"# n={N} c={c} {uplink}: allrows "
              f"{row['allrows_us']/1e3:.1f}ms gather "
              f"{row['gather_us']/1e3:.1f}ms "
              f"({row['speedup_gather_vs_allrows']:.2f}x)", flush=True)

smallest_c = min(CS)
accept = min(r["speedup_gather_vs_allrows"] for r in rows
             if r["c"] == smallest_c)
min_sub = min((r["speedup_gather_vs_allrows"] for r in rows
               if r["c"] < N), default=0.0)
out = {
    "rows": rows,
    "speedup_at_quarter_cohort": accept,
    "min_speedup_any_partial_row": min_sub,
    # the c == n gather rows time pure gather/scatter overhead; recorded,
    # not gated (expected ~1x)
    "full_cohort_gather_ratio": [
        r["speedup_gather_vs_allrows"] for r in rows if r["c"] == N
    ],
    "acceptance": {"quarter_cohort_min": 1.8, "any_partial_row_min": 1.0},
    "config": {"n": N, "cs": list(CS), "L": L, "s": S, "arch": "dense",
               "d_model": cfg.d_model, "n_layers": cfg.n_layers,
               "seq_len": dcfg.seq_len,
               "per_client_batch": dcfg.per_client_batch, "reps": REPS},
}
print(json.dumps(out))
"""


def _bench(smoke: bool) -> dict:
    return child_json(_CODE, what="elastic bench", smoke=smoke)


def run(paper_scale: bool = False, smoke: bool = False):
    del paper_scale
    art = _bench(smoke=smoke)
    if not smoke:  # smoke runs must not clobber the measured artifact
        with open(ARTIFACT, "w") as f:
            json.dump(art, f, indent=1)
    rows = []
    for r in art["rows"]:
        tag = f"elastic/n{r['n']}/c{r['c']}/{r['uplink']}"
        derived = f"L={r['L']},s={r['s']}"
        rows.append({"name": f"{tag}/allrows",
                     "us_per_call": r["allrows_us"], "derived": derived})
        rows.append({"name": f"{tag}/gather",
                     "us_per_call": r["gather_us"], "derived": derived})
        rows.append({
            "name": f"{tag}/speedup_gather_vs_allrows",
            "us_per_call": round(r["speedup_gather_vs_allrows"], 3),
            "derived": ("acceptance: >= 1.8 at c=n/4, >= 1.0 at any c < n;"
                        " c == n rows record gather/scatter overhead"),
        })
    return rows


if __name__ == "__main__":
    for r in run(smoke=os.environ.get("REPRO_BENCH_SMOKE") == "1"):
        print(r)
