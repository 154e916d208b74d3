"""Round-engine benchmark: the fused scanned round vs the seed per-step
driver, at reduced gemma2-2b on the 8-device host mesh.

Three per-round wall-time measurements at fixed L = 4 local steps, written
to ``BENCH_round_engine.json`` at the repo root and emitted as CSV rows via
``benchmarks/run.py``:

  per_step           the seed driver: one un-donated jit dispatch per local
                     step, host-side Markov sampling between steps, comm
                     step dispatched separately.
  fused_host_data    the engine's scanned round (donated state, comm step in
                     the same program) fed a host-sampled stacked batch once
                     per round — isolates the scan + donation win.
  fused_device_data  the full engine (`rounds.make_round_fn`): data sampled
                     on device inside the scan from carried PRNG keys; zero
                     steady-state host->device transfers.

Also records the compile-cache footprint across 30 geometric rounds
(acceptance: <= log2(max_L) + 1 distinct programs).

Runs in a subprocess so this process keeps the single real CPU device.
"""

from __future__ import annotations

import json
import os

from benchmarks.common import child_json

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
ARTIFACT = os.path.join(REPO, "BENCH_round_engine.json")

_CODE = r"""
import json, math, sys, time
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.data import DataConfig, SyntheticTokenPipeline, device_sampler
from repro.dist import rounds, sharding, tamuna_dp
from repro.launch.mesh import make_host_mesh

L, ROUNDS, WARM, MAX_L = 4, 10, 3, 16
mesh = make_host_mesh(4, 2)
cfg = registry.get_reduced_config("gemma2-2b")
n = sharding.n_clients(mesh)
tcfg = tamuna_dp.DistTamunaConfig(gamma=0.05, c=3, s=2, p=0.34)
dcfg = DataConfig(seq_len=64, per_client_batch=2, vocab=min(cfg.vocab, 512),
                  seed=0)
pipe = SyntheticTokenPipeline(dcfg, cfg, mesh)

def fresh_state():
    st = tamuna_dp.init_state(jax.random.key(0), cfg, mesh, tcfg)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                      tamuna_dp.state_pspecs(st, cfg, mesh),
                      is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(st, sh)

out = {}

# --- per_step: the seed driver (un-donated jits, host sampling per step)
state = fresh_state()
local = jax.jit(tamuna_dp.make_local_step(cfg, tcfg))
comm = jax.jit(tamuna_dp.make_comm_step(cfg, tcfg, mesh))

def per_step_round(state, r):
    for _ in range(L):
        state, m = local(state, **pipe.next_batch())
    return comm(state, jax.random.key_data(jax.random.key(r)))

for r in range(WARM):
    state = per_step_round(state, r)
jax.block_until_ready(state.round)
t0 = time.perf_counter()
for r in range(WARM, WARM + ROUNDS):
    state = per_step_round(state, r)
jax.block_until_ready(state.round)
out["per_step"] = (time.perf_counter() - t0) / ROUNDS * 1e6

# --- fused_host_data: scanned donated round fed stacked host batches
def make_fused_host(cfg, tcfg, mesh):
    local_raw = tamuna_dp.make_local_step(cfg, tcfg)
    comm_raw = tamuna_dp.make_comm_step(cfg, tcfg, mesh)
    def fn(state, batches, key_data):
        def body(st, batch):
            st, m = local_raw(st, **batch)
            return st, m["loss"]
        state, losses = jax.lax.scan(body, state, batches)
        return comm_raw(state, key_data), losses.mean()
    return jax.jit(fn, donate_argnums=(0,))

fused_host = make_fused_host(cfg, tcfg, mesh)

def stack_batches():
    bs = [pipe.next_batch() for _ in range(L)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *bs)

state = fresh_state()
for r in range(WARM):
    state, _ = fused_host(state, stack_batches(),
                          jax.random.key_data(jax.random.key(r)))
jax.block_until_ready(state.round)
t0 = time.perf_counter()
for r in range(WARM, WARM + ROUNDS):
    state, _ = fused_host(state, stack_batches(),
                          jax.random.key_data(jax.random.key(r)))
jax.block_until_ready(state.round)
out["fused_host_data"] = (time.perf_counter() - t0) / ROUNDS * 1e6

# --- fused_device_data: the full engine, on-device sampling from the carry
round_fn = rounds.make_round_fn(
    cfg, tcfg, mesh, sample_batch=device_sampler(dcfg, cfg, mesh),
    max_L=MAX_L)
data = pipe.device_data()
carry = rounds.init_carry(fresh_state(), jax.random.key(1), flush_every=8)
for r in range(WARM):
    carry = round_fn(carry, data, L, r % 8)
jax.block_until_ready(carry.state.round)
t0 = time.perf_counter()
for r in range(WARM, WARM + ROUNDS):
    carry = round_fn(carry, data, L, r % 8)
jax.block_until_ready(carry.state.round)
out["fused_device_data"] = (time.perf_counter() - t0) / ROUNDS * 1e6

# --- compile-cache bound across geometric round lengths
rng = np.random.default_rng(0)
for r in range(30):
    Lr = tamuna_dp.sample_round_length(rng, tcfg.p, max_L=MAX_L)
    carry = round_fn(carry, data, Lr, 0)
jax.block_until_ready(carry.state.round)
out["distinct_compilations"] = len(round_fn.cache)
out["compile_cache_bound"] = int(math.log2(MAX_L)) + 1
out["config"] = {"arch": cfg.name, "n": n, "L": L, "rounds": ROUNDS,
                 "max_L": MAX_L, "c": tcfg.c, "s": tcfg.s,
                 "seq_len": dcfg.seq_len,
                 "per_client_batch": dcfg.per_client_batch}
out["speedup_fused_vs_per_step"] = out["per_step"] / out["fused_device_data"]
print(json.dumps(out))
"""


def _bench() -> dict:
    return child_json(_CODE, what="round_engine bench", devices=8)


def run(paper_scale: bool = False):
    del paper_scale
    art = _bench()
    with open(ARTIFACT, "w") as f:
        json.dump(art, f, indent=1)
    cfg = art["config"]
    derived = (f"arch={cfg['arch']},n={cfg['n']},L={cfg['L']},"
               f"seq={cfg['seq_len']}")
    rows = [
        {"name": f"round_engine/{k}", "us_per_call": art[k],
         "derived": derived}
        for k in ("per_step", "fused_host_data", "fused_device_data")
    ]
    rows.append({
        "name": "round_engine/speedup_fused_vs_per_step",
        "us_per_call": round(art["speedup_fused_vs_per_step"], 3),
        "derived": "acceptance: >= 2.0",
    })
    rows.append({
        "name": "round_engine/distinct_compilations",
        "us_per_call": art["distinct_compilations"],
        "derived": (f"30 geometric rounds, max_L={cfg['max_L']}; "
                    f"acceptance: <= {art['compile_cache_bound']}"),
    })
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
