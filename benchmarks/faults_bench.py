"""Fault-tolerance benchmark: convergence under mid-round dropout.

Runs the strongly-convex logistic-regression TAMUNA loop (Theorem-3 tuned
parameters) with the *dist* comm step — ``comm_ws.cyclic_comm`` on the
flat client-stacked vector state — under a deterministic ``FaultPlan``
(DESIGN.md §12), sweeping Bernoulli uplink dropout p_fail in
{0, 0.1, 0.2, 0.4} across three drivers:

  fault-free  no drops: the reference rounds-to-target,
  quorum      survivor-aware aggregation (per-coordinate arrived-owner
              means, uncovered coordinates hold the previous server
              model) + cohort resample with capped exponential backoff
              when arrivals fall below c//2 + 1,
  wait_all    the biased control: whatever arrived is aggregated at the
              legacy 1/s scale, so dropped owners pull their coordinates
              toward zero — the failure mode survivor correction exists
              to fix.

Per scenario the artifact records rounds-to-target (suboptimality below
``target_rel`` x the initial gap), retries, quorum misses, and simulated
wall clock (unit step cost + retry backoff).  Acceptance: at
p_fail = 0.2 the quorum driver reaches target within 2x the fault-free
round count, while the wait_all control either never reaches it or ends
with a suboptimality >= 10x the target.  Deterministic replay: the
p_fail = 0.2 quorum run is executed twice and must match bitwise.

Writes ``BENCH_faults.json``; ``run(smoke=True)`` (or
``REPRO_BENCH_SMOKE=1``) shrinks the problem and skips the artifact
write — wired into tests/test_bench_tooling.py.
"""

from __future__ import annotations

import json
import os

from benchmarks.common import child_json

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
ARTIFACT = os.path.join(REPO, "BENCH_faults.json")

_CODE = r"""
import json, os
import numpy as np
import jax, jax.numpy as jnp

from repro.core import problems, tamuna
from repro.dist import comm_ws
from repro.dist.cohort import CohortPlan
from repro.dist.faults import FaultModel, FaultPlan

SMOKE = os.environ.get("REPRO_BENCH_SMOKE") == "1"
N, D, SPC = (8, 16, 4) if SMOKE else (16, 32, 8)
KAPPA = 50.0 if SMOKE else 100.0
MAX_ROUNDS = 80 if SMOKE else 4000
TARGET_REL = 1e-1 if SMOKE else 1e-3
P_FAILS = (0.0, 0.2) if SMOKE else (0.0, 0.1, 0.2, 0.4)
MAX_RETRIES, BACKOFF0 = 3, 1.0

prob = problems.make_logreg_problem(
    n=N, d=D, samples_per_client=SPC, kappa=KAPPA, seed=0
)
C = max(2, N // 4)
cfg = tamuna.TamunaConfig.tuned(prob, c=C)
L = max(1, round(1.0 / cfg.p))
Q = C // 2 + 1
scale = cfg.eta / cfg.gamma
target = float(prob.suboptimality(jnp.zeros(D))) * TARGET_REL


@jax.jit
def local_steps(x_bar, h, cohort):
    Xc = jnp.broadcast_to(x_bar, (C, D))
    hc = h[cohort]

    def body(i, Xc):
        return Xc - cfg.gamma * prob.cohort_grads(Xc, cohort) \
            + cfg.gamma * hc

    return jax.lax.fori_loop(0, L, body, Xc)


def comm_step(correct):
    @jax.jit
    def step(x_bar, h, Xc, cohort, slot, arrived):
        # non-cohort rows sit at x_bar, so after the comm any idle row
        # reads back as "covered coords updated, uncovered keep the old
        # server model" -- exactly the survivor-aware server state
        X = jnp.broadcast_to(x_bar, (N, D)).at[cohort].set(Xc)
        x_new, h_new = comm_ws.cyclic_comm(
            X, h, slot, C, cfg.s, scale, impl="ws",
            arrived=arrived, correct=correct,
        )
        return x_new, h_new

    return step


def comm_step_clean():
    @jax.jit
    def step(x_bar, h, Xc, cohort, slot):
        X = jnp.broadcast_to(x_bar, (N, D)).at[cohort].set(Xc)
        return comm_ws.cyclic_comm(X, h, slot, C, cfg.s, scale, impl="ws")

    return step


def run_driver(p_fail, policy, seed=3):
    faults = FaultPlan(seed=seed, n=N, model=FaultModel(p_drop=p_fail))
    plan = CohortPlan(seed=7, n=N, c=C)
    faulted = p_fail > 0.0
    step = (comm_step(policy == "quorum") if faulted
            else comm_step_clean())
    x_bar = jnp.zeros(D)
    h = jnp.zeros((N, D))
    retries = quorum_miss = 0
    clock = 0.0
    hit = None
    subs = []
    for g in range(MAX_ROUNDS):
        attempt, backoff = 0, 0.0
        while True:
            cohort = np.asarray(plan.cohort(g, attempt))
            member = np.zeros(N, bool)
            member[cohort] = True
            arrived = member & ~faults.drops(g, attempt)
            if (policy == "quorum" and int(arrived.sum()) < Q
                    and attempt < MAX_RETRIES):
                quorum_miss += 1
                backoff += BACKOFF0 * (2.0 ** attempt)
                attempt += 1
                continue
            break
        retries += attempt
        clock += float(L) + backoff
        cohort_j = jnp.asarray(cohort, jnp.int32)
        # fresh ownership permutation per round (paper Alg. 1 line 10:
        # the unbiasedness of the compressed aggregate needs it; a fixed
        # template stalls ~4 orders of magnitude above the target)
        perm = np.random.default_rng(
            np.random.SeedSequence([7, 97, g, attempt])
        ).permutation(C)
        slot_np = np.full(N, -1, np.int64)
        slot_np[cohort] = perm
        slot = jnp.asarray(slot_np, jnp.int32)
        Xc = local_steps(x_bar, h, cohort_j)
        if faulted:
            x_new, h = step(x_bar, h, Xc, cohort_j, slot,
                            jnp.asarray(arrived))
        else:
            x_new, h = step(x_bar, h, Xc, cohort_j, slot)
        # read the server model off an idle row: covered coords carry the
        # aggregate, uncovered coords kept that row's x_bar
        idle = int(np.setdiff1d(np.arange(N), cohort)[0])
        x_bar = x_new[idle]
        sub = float(prob.suboptimality(x_bar))
        subs.append(sub)
        if hit is None and sub < target:
            hit = g + 1
            break
    return {
        "p_fail": p_fail, "policy": policy,
        "rounds_to_target": hit, "final_suboptimality": subs[-1],
        "retries": retries, "quorum_miss": quorum_miss,
        "sim_clock": clock,
        "x_fingerprint": [float(v) for v in np.asarray(x_bar)[:4]],
    }


rows = [run_driver(0.0, "fault_free")]
base = rows[0]["rounds_to_target"]
for pf in P_FAILS:
    if pf == 0.0:
        continue
    for policy in ("quorum", "wait_all"):
        rows.append(run_driver(pf, policy))
for r in rows:
    print(f"# p_fail={r['p_fail']} {r['policy']}: rounds="
          f"{r['rounds_to_target']} final={r['final_suboptimality']:.3e} "
          f"retries={r['retries']} clock={r['sim_clock']:.0f}",
          flush=True)

# deterministic replay: identical seeds => bitwise-identical trajectory
pf_chk = 0.2 if 0.2 in P_FAILS else max(P_FAILS)
a = run_driver(pf_chk, "quorum")
b = run_driver(pf_chk, "quorum")
replay_ok = (a["rounds_to_target"] == b["rounds_to_target"]
             and a["x_fingerprint"] == b["x_fingerprint"])

by = {(r["p_fail"], r["policy"]): r for r in rows}
q02 = by.get((0.2, "quorum"))
w02 = by.get((0.2, "wait_all"))
ratio = (q02["rounds_to_target"] / base
         if q02 and q02["rounds_to_target"] and base else None)
control_fails = (w02 is not None and (
    w02["rounds_to_target"] is None
    or w02["final_suboptimality"] >= 10 * target))
out = {
    "rows": rows,
    "target": target,
    "fault_free_rounds": base,
    "quorum_ratio_at_p02": ratio,
    "wait_all_control_stalls_at_p02": control_fails,
    "deterministic_replay_ok": replay_ok,
    "acceptance": {"quorum_ratio_max": 2.0,
                   "control_must_stall_or_bias": True,
                   "replay_bitwise": True},
    "config": {"n": N, "d": D, "c": C, "s": cfg.s, "L": L, "quorum": Q,
               "kappa": KAPPA, "target_rel": TARGET_REL,
               "max_rounds": MAX_ROUNDS, "p_fails": list(P_FAILS),
               "max_retries": MAX_RETRIES, "backoff0": BACKOFF0},
}
print(json.dumps(out))
"""


def _bench(smoke: bool) -> dict:
    return child_json(_CODE, what="faults bench", smoke=smoke)


def run(paper_scale: bool = False, smoke: bool = False):
    del paper_scale
    art = _bench(smoke=smoke)
    if not smoke:  # smoke runs must not clobber the measured artifact
        with open(ARTIFACT, "w") as f:
            json.dump(art, f, indent=1)
    rows = []
    for r in art["rows"]:
        tag = f"faults/p{r['p_fail']}/{r['policy']}"
        reached = r["rounds_to_target"]
        rows.append({
            "name": tag,
            "us_per_call": float(reached if reached is not None else -1),
            "derived": (f"rounds_to_target={reached} "
                        f"final={r['final_suboptimality']:.2e} "
                        f"retries={r['retries']} "
                        f"clock={r['sim_clock']:.0f}"),
        })
    ratio = art.get("quorum_ratio_at_p02")
    rows.append({
        "name": "faults/quorum_ratio_at_p02",
        "us_per_call": round(ratio, 3) if ratio is not None else -1.0,
        "derived": ("acceptance: <= 2.0x fault-free rounds; control "
                    f"stalls={art.get('wait_all_control_stalls_at_p02')} "
                    f"replay_ok={art.get('deterministic_replay_ok')}"),
    })
    return rows


if __name__ == "__main__":
    for r in run(smoke=os.environ.get("REPRO_BENCH_SMOKE") == "1"):
        print(r)
