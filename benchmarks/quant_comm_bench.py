"""Quantized-wire benchmark: wire bytes, round time, and convergence
floor vs payload width (DESIGN.md §13).

Three measurements, one artifact (``BENCH_quant_comm.json``):

  bytes        per-round UpCom/DownCom wire bytes per client, read off the
               comm step's dtype-aware accounting counters (NOT recomputed
               on the host) at reduced gemma2-2b on the 4x2 host mesh, for
               wire_precision in {f32, bf16, f16, int8, auto}.  Headline:
               ``up_bytes_ratio_int8_vs_f32`` (acceptance >= 3.5x — int8
               codes + one f32 scale per 256-coordinate chunk).
  timing       fused-round wall time (``rounds.make_round_fn``: L scanned
               local steps + comm step, donated state) f32 vs int8 on the
               same mesh.  Acceptance: round_time_ratio <= 1.10 — the
               quantize/dequant work amortizes over the local steps.  The
               comm-step-only ratio is recorded as an informational row:
               on CPU the int8 hash-draw + code packing is NOT free at the
               step level (the EXPERIMENTS.md negative result); the claim
               is about the round, which is what the trainer dispatches.
  convergence  the floor sweep: strongly convex logreg (Theorem-3 tuned
               TAMUNA, same problem family as BENCH_faults) run at
               wire_precision in {f32, f16, int8, int4} for the SAME
               number of rounds R (R = rounds for f32 to reach
               ``TARGET_REL`` x the initial gap).  Records the converged
               suboptimality floor per width (min over the trailing
               window).  Acceptance: floor(int8) <= 10 x floor(f32) at
               matched rounds; int4's higher floor is the expected
               variance-vs-bits tradeoff and is recorded, not gated.

``run(smoke=True)`` (or ``REPRO_BENCH_SMOKE=1``) shrinks every problem
and skips the artifact write — wired into tests/test_bench_tooling.py.
"""

from __future__ import annotations

import json
import os

from benchmarks.common import child_json

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
ARTIFACT = os.path.join(REPO, "BENCH_quant_comm.json")

# --- meshed subprocess: byte accounting + fused-round timing (8 devices)
_MESHED_CODE = r"""
import json, os, time
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.data import DataConfig, SyntheticTokenPipeline, device_sampler
from repro.dist import rounds, sharding, tamuna_dp, wire
from repro.launch.mesh import make_host_mesh

SMOKE = os.environ.get("REPRO_BENCH_SMOKE") == "1"
DP, MP = (2, 1) if SMOKE else (4, 2)
# L = round(1/p): the paper's local-training regime (many local steps
# per comm round) is what amortizes the wire codec over the round
L, ROUNDS, WARM = (2, 2, 1) if SMOKE else (8, 10, 3)
P_GEOM = 0.5 if SMOKE else 0.125
mesh = make_host_mesh(DP, MP)
cfg = registry.get_reduced_config("gemma2-2b")
n = sharding.n_clients(mesh)
dcfg = DataConfig(seq_len=64, per_client_batch=2,
                  vocab=min(cfg.vocab, 512), seed=0)

def tcfg_for(policy):
    return tamuna_dp.DistTamunaConfig(
        gamma=0.05, c=max(2, (3 * n) // 4), s=2, p=P_GEOM,
        wire_precision=policy)

def fresh_state(tcfg):
    st = tamuna_dp.init_state(jax.random.key(0), cfg, mesh, tcfg)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                      tamuna_dp.state_pspecs(st, cfg, mesh),
                      is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(st, sh)

# --- bytes: one comm step per policy, read the state counters
bytes_rows = []
for policy in ("f32", "bf16", "f16", "int8", "auto"):
    tcfg = tcfg_for(policy)
    st = fresh_state(tcfg)
    raw = tamuna_dp.make_comm_step(cfg, tcfg, mesh)
    out = jax.jit(raw)(st, jax.random.key_data(jax.random.key(7)))
    kinds = list(raw.wire_kinds)
    bytes_rows.append({
        "policy": policy,
        "up_bytes_per_round": float(out.up_bytes),
        "down_bytes_per_round": float(out.down_bytes),
        "up_floats_per_round": float(out.up_floats),
        "leaf_kind_counts": {k: kinds.count(k) for k in sorted(set(kinds))},
    })
    print(f"# bytes {policy}: up={float(out.up_bytes):.3e} "
          f"down={float(out.down_bytes):.3e} "
          f"(floats*4={float(out.up_floats)*4:.3e})", flush=True)
by_policy = {r["policy"]: r for r in bytes_rows}
up_ratio = (by_policy["f32"]["up_bytes_per_round"]
            / by_policy["int8"]["up_bytes_per_round"])

# --- timing: fused round f32 vs int8 (+ comm-step-only, informational)
pipe = SyntheticTokenPipeline(dcfg, cfg, mesh)
data = pipe.device_data()
round_us, comm_us = {}, {}
for policy in ("f32", "int8"):
    tcfg = tcfg_for(policy)
    round_fn = rounds.make_round_fn(
        cfg, tcfg, mesh, sample_batch=device_sampler(dcfg, cfg, mesh),
        max_L=8)
    carry = rounds.init_carry(fresh_state(tcfg), jax.random.key(1),
                              flush_every=8)
    for r in range(WARM):
        carry = round_fn(carry, data, L, r % 8)
    jax.block_until_ready(carry.state.round)
    ts = []
    for r in range(ROUNDS):
        t0 = time.perf_counter()
        carry = round_fn(carry, data, L, r % 8)
        jax.block_until_ready(carry.state.round)
        ts.append(time.perf_counter() - t0)
    round_us[policy] = float(np.min(ts)) * 1e6

    comm = jax.jit(tamuna_dp.make_comm_step(cfg, tcfg, mesh),
                   donate_argnums=(0,))
    st = fresh_state(tcfg)
    for r in range(WARM):
        st = comm(st, jax.random.key_data(jax.random.key(r)))
    jax.block_until_ready(st.round)
    ts = []
    for r in range(ROUNDS):
        t0 = time.perf_counter()
        st = comm(st, jax.random.key_data(jax.random.key(r)))
        jax.block_until_ready(st.round)
        ts.append(time.perf_counter() - t0)
    comm_us[policy] = float(np.min(ts)) * 1e6
    print(f"# timing {policy}: round {round_us[policy]/1e3:.1f}ms "
          f"comm {comm_us[policy]/1e3:.1f}ms", flush=True)

out = {
    "bytes_rows": bytes_rows,
    "up_bytes_ratio_int8_vs_f32": up_ratio,
    "round_us": round_us,
    "comm_us": comm_us,
    "round_time_ratio_int8_vs_f32": round_us["int8"] / round_us["f32"],
    "comm_time_ratio_int8_vs_f32": comm_us["int8"] / comm_us["f32"],
    "config": {"arch": cfg.name, "mesh": f"{DP}x{MP}", "L": L,
               "rounds": ROUNDS, "n": n},
}
print(json.dumps(out))
"""

# --- convergence subprocess: floor vs bits on convex logreg (1 device)
_CONV_CODE = r"""
import json, os
import numpy as np
import jax, jax.numpy as jnp

from repro.core import problems, tamuna
from repro.dist import comm_ws, wire

SMOKE = os.environ.get("REPRO_BENCH_SMOKE") == "1"
N, D, SPC = (8, 16, 4) if SMOKE else (16, 32, 8)
KAPPA = 50.0 if SMOKE else 100.0
MAX_ROUNDS = 60 if SMOKE else 4000
TARGET_REL = 1e-1 if SMOKE else 1e-3
KINDS = ("f32", "int8") if SMOKE else ("f32", "f16", "int8", "int4")
TAIL = 5 if SMOKE else 20

prob = problems.make_logreg_problem(
    n=N, d=D, samples_per_client=SPC, kappa=KAPPA, seed=0
)
C = max(2, N // 4)
cfg = tamuna.TamunaConfig.tuned(prob, c=C)
L = max(1, round(1.0 / cfg.p))
scale = cfg.eta / cfg.gamma
gap0 = float(prob.suboptimality(jnp.zeros(D)))
target = gap0 * TARGET_REL


@jax.jit
def local_steps(x_bar, h, cohort):
    Xc = jnp.broadcast_to(x_bar, (C, D))
    hc = h[cohort]

    def body(i, Xc):
        return Xc - cfg.gamma * prob.cohort_grads(Xc, cohort) \
            + cfg.gamma * hc

    return jax.lax.fori_loop(0, L, body, Xc)


def comm_step(kind):
    wired = wire.is_wire(kind)

    @jax.jit
    def step(x_bar, h, Xc, cohort, slot, wseed):
        X = jnp.broadcast_to(x_bar, (N, D)).at[cohort].set(Xc)
        return comm_ws.cyclic_comm(
            X, h, slot, C, cfg.s, scale, impl="ws",
            wire=kind if wired else None,
            wire_seed=wseed if wired else None,
        )

    return step


def run_kind(kind, rounds, seed=3):
    step = comm_step(kind)
    rng = np.random.default_rng(seed)
    x_bar = jnp.zeros(D)
    h = jnp.zeros((N, D))
    subs = []
    hit = None
    for g in range(rounds):
        cohort = rng.choice(N, size=C, replace=False)
        slot_np = np.full(N, -1, np.int64)
        slot_np[cohort] = rng.permutation(C)
        slot = jnp.asarray(slot_np, jnp.int32)
        cohort_j = jnp.asarray(cohort, jnp.int32)
        wseed = wire.round_seed(
            jax.random.fold_in(jax.random.key(g), wire.WIRE_FOLD))
        Xc = local_steps(x_bar, h, cohort_j)
        x_new, h = step(x_bar, h, Xc, cohort_j, slot, wseed)
        idle = int(np.setdiff1d(np.arange(N), cohort)[0])
        x_bar = x_new[idle]
        subs.append(float(prob.suboptimality(x_bar)))
        if hit is None and subs[-1] < target:
            hit = g + 1
            if kind == "f32":
                break
    floor = float(np.min(subs[-TAIL:]))
    return {"kind": kind,
            "bits": {"f32": 32, "f16": 16, "int8": 8, "int4": 4}[kind],
            "rounds": len(subs), "rounds_to_target": hit,
            "final_suboptimality": subs[-1], "floor": floor}


# R = rounds for the f32 wire to hit target; every width runs exactly R
f32_probe = run_kind("f32", MAX_ROUNDS)
R = f32_probe["rounds_to_target"] or MAX_ROUNDS
rows = [run_kind(k, R) for k in KINDS]
for r in rows:
    print(f"# conv {r['kind']} ({r['bits']}b): floor={r['floor']:.3e} "
          f"final={r['final_suboptimality']:.3e} rounds={r['rounds']}",
          flush=True)
by = {r["kind"]: r for r in rows}
out = {
    "rows": rows,
    "matched_rounds": R,
    "target": target,
    "initial_gap": gap0,
    "floor_ratio_int8_vs_f32": by["int8"]["floor"] / by["f32"]["floor"],
    "config": {"n": N, "d": D, "c": C, "s": cfg.s, "L": L,
               "kappa": KAPPA, "target_rel": TARGET_REL,
               "kinds": list(KINDS), "tail": TAIL},
}
print(json.dumps(out))
"""


def _bench(code: str, devices: int = 0, smoke: bool = False) -> dict:
    return child_json(code, what="quant_comm bench", devices=devices,
                      smoke=smoke)


def run(paper_scale: bool = False, smoke: bool = False):
    del paper_scale
    meshed = _bench(_MESHED_CODE, devices=2 if smoke else 8, smoke=smoke)
    conv = _bench(_CONV_CODE, smoke=smoke)
    art = {
        "meshed": meshed,
        "convergence": conv,
        "up_bytes_ratio_int8_vs_f32": meshed["up_bytes_ratio_int8_vs_f32"],
        "round_time_ratio_int8_vs_f32":
            meshed["round_time_ratio_int8_vs_f32"],
        "floor_ratio_int8_vs_f32": conv["floor_ratio_int8_vs_f32"],
        "acceptance": {"up_bytes_ratio_min": 3.5,
                       "round_time_ratio_max": 1.10,
                       "floor_ratio_max": 10.0},
    }
    if not smoke:  # smoke runs must not clobber the measured artifact
        with open(ARTIFACT, "w") as f:
            json.dump(art, f, indent=1)
    rows = []
    for r in meshed["bytes_rows"]:
        rows.append({
            "name": f"quant_comm/bytes/{r['policy']}",
            "us_per_call": r["up_bytes_per_round"],
            "derived": (f"down={r['down_bytes_per_round']:.3e} "
                        f"kinds={r['leaf_kind_counts']}"),
        })
    rows.append({
        "name": "quant_comm/up_bytes_ratio_int8_vs_f32",
        "us_per_call": round(art["up_bytes_ratio_int8_vs_f32"], 3),
        "derived": "acceptance: >= 3.5x",
    })
    for policy, us in meshed["round_us"].items():
        rows.append({
            "name": f"quant_comm/round/{policy}",
            "us_per_call": us,
            "derived": f"comm_only={meshed['comm_us'][policy]:.0f}us",
        })
    rows.append({
        "name": "quant_comm/round_time_ratio_int8_vs_f32",
        "us_per_call": round(art["round_time_ratio_int8_vs_f32"], 3),
        "derived": ("acceptance: <= 1.10 (fused round; comm-step-only "
                    f"ratio {meshed['comm_time_ratio_int8_vs_f32']:.2f} "
                    "is informational — CPU int8 packing is not free)"),
    })
    for r in conv["rows"]:
        rows.append({
            "name": f"quant_comm/floor/{r['kind']}",
            "us_per_call": r["floor"],
            "derived": (f"bits={r['bits']} rounds={r['rounds']} "
                        f"final={r['final_suboptimality']:.3e}"),
        })
    rows.append({
        "name": "quant_comm/floor_ratio_int8_vs_f32",
        "us_per_call": round(art["floor_ratio_int8_vs_f32"], 3),
        "derived": (f"acceptance: <= 10x at matched "
                    f"rounds={conv['matched_rounds']}"),
    })
    return rows


if __name__ == "__main__":
    for r in run(smoke=os.environ.get("REPRO_BENCH_SMOKE") == "1"):
        print(r)
