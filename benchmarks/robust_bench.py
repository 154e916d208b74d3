"""Byzantine-robustness benchmark: convergence under adversarial uplinks
(DESIGN.md §15).

Runs the strongly-convex logistic-regression TAMUNA loop with the *dist*
comm step (``comm_ws.cyclic_comm`` on the flat client-stacked state)
against a persistent Byzantine fraction ``f = 0.25`` of the fleet under
two attacks:

  sign_flip  adversaries negate their payload — norm-preserving, so no
             magnitude guard can see it; only the robust combiner helps,
  blowup     adversaries scale their payload by 1e8 — finite, so the
             nonfinite-only guard admits it; the adaptive magnitude
             guard (median + 6 * 1.4826 * MAD of arrived payload norms)
             demotes the rows before aggregation.

Per attack, three aggregators: ``mean`` (the plain survivor mean with
the nonfinite-only guard — the control that stalls or diverges),
``trimmed`` (k = c/3 per side, adaptive guard) and ``median`` (adaptive
guard).  The robust scenarios run in the redundancy regime ``s = c``
(no sparsification): per-coordinate order statistics need the honest
majority *inside every owner stack*, so under attack the loop trades
the compression knob for robustness — k = c/3 per side then tolerates
the worst-case per-round Byzantine fraction (all f*n adversaries drawn
into the cohort gives f*n/c = 1/3) even before reputation quarantines
the persistent offenders.

Attack rows are scored against the *honest-subset* optimum (solved to
machine precision by deterministic full-gradient descent over the
non-Byzantine clients): a persistent adversary never contributes its
honest data, so the full-problem optimum is unreachable in principle
and the honest-subset minimizer is the correct floor.  Fault-free rows
use the full optimum; both use the relative squared distance
``||x - x*||^2 / ||x0 - x*||^2 < TARGET_REL`` as the hit criterion.

Aggregation alone is not enough: a robust combiner breaks TAMUNA's
``sum_i h_i = 0`` control-variate invariant (the mean-combiner identity
that pins the fixed point to the optimizer), leaving a *permanent* bias
even after every adversary is quarantined.  The driver therefore
re-centers ``h`` over the active clients each round
(``robust.recenter_h``) — without it the robust runs plateau ~10x above
target; with it they converge to the honest optimum at machine
precision.

Acceptance: both robust aggregators reach their target within 2x the
fault-free round count while the mean control never does (or ends
>= 10x above target / nonfinite); the robust comm step costs <= 1.5x
the mean comm step at the production sparsified uplink shape (s=4,
TIME_D-wide payloads — the s=c redundancy regime is reported
alongside); ``trimmed k=0`` at ``f=0`` is bitwise identical to
``mean`` in all four comm impls (dense / ws / pallas / shard engine); a
robust scenario replayed from the same seeds matches bitwise; the int8
quantized wire composes (robust stats run on the dequantized values,
deviation stays at quantization scale).

Writes ``BENCH_robust.json``; ``run(smoke=True)`` (or
``REPRO_BENCH_SMOKE=1``) shrinks the problem and skips the artifact
write — wired into tests/test_bench_tooling.py and benchmarks/run.py
(``--only robust``).
"""

from __future__ import annotations

import json
import os

from benchmarks.common import child_json

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
ARTIFACT = os.path.join(REPO, "BENCH_robust.json")

_CODE = r"""
import json, os, time
import numpy as np
import jax, jax.numpy as jnp

from repro.core import problems, tamuna
from repro.dist import comm_ws, robust, wire
from repro.dist.cohort import CohortPlan
from repro.dist.faults import FaultModel, FaultPlan, adversarial_rows

SMOKE = os.environ.get("REPRO_BENCH_SMOKE") == "1"
N, D, SPC = (8, 16, 4) if SMOKE else (16, 32, 8)
KAPPA = 50.0 if SMOKE else 100.0
MAX_ROUNDS = 80 if SMOKE else 4000
TARGET_REL = 1e-1 if SMOKE else 1e-3
# cohort large enough that the worst-case Byzantine fraction of a round
# (all f*N adversaries drawn) stays below the 50% breakdown point of the
# median/MAD guard and the per-coordinate order statistics
C = max(4, (3 * N) // 4)
# robustness regime: s = c (no sparsification) so every coordinate's
# owner stack carries the full cohort redundancy; k = c/3 per side then
# survives the worst-case per-round Byzantine draw (f*n/c = 1/3)
S = C
TRIM_K = C // 3
F_BYZ = 0.25
TIME_D = 4096 if SMOKE else 65536
TIME_ITERS = 10 if SMOKE else 30

prob = problems.make_logreg_problem(
    n=N, d=D, samples_per_client=SPC, kappa=KAPPA, seed=0
)
cfg = tamuna.TamunaConfig.tuned(prob, c=C, s=S)
L = max(1, round(1.0 / cfg.p))
scale = cfg.eta / cfg.gamma

# Byzantine set is a function of the fault seed alone — shared by every
# attack row so the honest-subset reference is computed once
BYZ = FaultPlan(
    seed=3, n=N, model=FaultModel(adversary="sign_flip", f_byz=F_BYZ)
).byzantine
HONEST = np.flatnonzero(~BYZ)


def solve_subset(idx, iters=20000):
    # full-gradient descent on the subset mean objective; each f_i is
    # L-smooth and mu-strongly convex, so step 1/L contracts linearly
    idx_j = jnp.asarray(idx, jnp.int32)

    @jax.jit
    def gd(x):
        def body(i, x):
            G = prob.grad_all_local(jnp.broadcast_to(x, (N, D)))
            return x - (1.0 / prob.L) * G[idx_j].mean(axis=0)

        return jax.lax.fori_loop(0, iters, body, x)

    return gd(jnp.zeros_like(prob.x_star))


X_STAR_FULL = prob.x_star
X_STAR_HONEST = solve_subset(HONEST)


@jax.jit
def local_steps(x_bar, h, cohort):
    Xc = jnp.broadcast_to(x_bar, (C, D))
    hc = h[cohort]

    def body(i, Xc):
        return Xc - cfg.gamma * prob.cohort_grads(Xc, cohort) \
            + cfg.gamma * hc

    return jax.lax.fori_loop(0, L, body, Xc)


def comm_step(spec):
    @jax.jit
    def step(x_bar, h, Xc, cohort, slot, arrived):
        X = jnp.broadcast_to(x_bar, (N, D)).at[cohort].set(Xc)
        return comm_ws.cyclic_comm(
            X, h, slot, C, S, scale, impl="ws",
            arrived=arrived, correct=True, robust=spec,
        )

    return step


def attack_rows(X, byz_member, member, attack):
    if attack == "sign_flip":
        return adversarial_rows(
            {"x": X}, byz_member, member & ~byz_member, "sign_flip"
        )["x"]
    return adversarial_rows(  # finite blowup: scale by 1e8
        {"x": X}, byz_member, member & ~byz_member, "scale",
        byz_scale=1e8,
    )["x"]


def run_driver(attack, agg):
    spec = robust.normalize_robust(
        agg, TRIM_K if agg == "trimmed" else 0, S
    )
    robust_run = attack != "none" and agg != "mean"
    guard = "adaptive" if robust_run else "nonfinite"
    byz = BYZ if attack != "none" else np.zeros(N, bool)
    # attack rows chase the honest-subset optimum (the reachable floor);
    # fault-free rows chase the full optimum
    x_ref = X_STAR_HONEST if attack != "none" else X_STAR_FULL
    err0 = float(jnp.sum(x_ref * x_ref))
    plan = CohortPlan(seed=7, n=N, c=C)
    # the full §15 stack for robust runs: combiner + adaptive guard +
    # anomaly-driven reputation quarantining persistent adversaries (the
    # combiner alone bounds per-round damage; quarantine removes the
    # variance floor a persistent f=0.25 attack would otherwise leave)
    rep = robust.Reputation(N, threshold=3.0, base_rounds=16,
                            max_doublings=6) if robust_run else None
    quarantined_ever = np.zeros(N, bool)
    step = comm_step(spec)
    x_bar = jnp.zeros(D)
    h = jnp.zeros((N, D))
    hit = None
    diverged = False
    guarded = 0
    err = float("nan")
    sub = float("nan")
    for g in range(MAX_ROUNDS):
        cohort = np.asarray(plan.cohort(g))
        member = np.zeros(N, bool)
        member[cohort] = True
        cohort_j = jnp.asarray(cohort, jnp.int32)
        perm = np.random.default_rng(
            np.random.SeedSequence([7, 97, g])
        ).permutation(C)
        slot_np = np.full(N, -1, np.int64)
        slot_np[cohort] = perm
        slot = jnp.asarray(slot_np, jnp.int32)
        Xc = local_steps(x_bar, h, cohort_j)
        X = jnp.broadcast_to(x_bar, (N, D)).at[cohort_j].set(Xc)
        arrived = member.copy()
        bad = np.zeros(N, bool)
        if attack != "none" and (byz & member).any():
            X = attack_rows(X, jnp.asarray(byz & member),
                            jnp.asarray(member), attack)
            Xc = X[cohort_j]
            if guard == "adaptive":
                bad = np.asarray(robust.magnitude_outliers(
                    {"x": X}, jnp.asarray(arrived)))
                guarded += int(bad.sum())
                arrived &= ~bad
        x_new, h = step(x_bar, h, Xc, cohort_j, slot,
                        jnp.asarray(arrived))
        if rep is not None:
            anom = np.asarray(robust.anomaly_scores(
                {"x": X}, jnp.asarray(arrived)))
            # a guard hit is hard evidence: score it above threshold so
            # guarded rows (excluded from the anomaly stats) still
            # accumulate reputation strikes
            an = anom.copy()
            an[bad] = 2.0 * rep.threshold
            for cid, w in rep.update(an, arrived | bad):
                plan.quarantine([cid], g + 1, g + w)
                quarantined_ever[cid] = True
            # robust combining breaks the sum(h)=0 invariant that pins
            # the fixed point to the optimizer; repair it each round
            # over the clients still in play (see robust.recenter_h)
            h = robust.recenter_h(h, jnp.asarray(~quarantined_ever))
        idle = np.setdiff1d(np.arange(N), cohort)
        x_bar = x_new[int(idle[0])] if idle.size else x_new[0]
        delta = x_bar.astype(x_ref.dtype) - x_ref
        err = float(jnp.sum(delta * delta)) / err0
        sub = float(prob.suboptimality(x_bar))
        if not np.isfinite(err):
            diverged = True
            break
        if err < TARGET_REL:
            hit = g + 1
            break
    qids = sorted({int(i) for ids, _, _ in plan._quarantine
                   for i in ids})
    return {
        "attack": attack, "agg": agg, "f_byz": F_BYZ if attack != "none"
        else 0.0, "guard": guard,
        "rounds_to_target": hit, "final_err_rel": err,
        "final_suboptimality": sub,
        "diverged": diverged, "guarded_rows": guarded,
        "quarantine_windows": len(plan._quarantine),
        "quarantined_byz_only": bool(all(byz[i] for i in qids))
        if qids else None,
        "x_fingerprint": [float(v) for v in np.asarray(x_bar)[:4]]
        if np.isfinite(np.asarray(x_bar)).all() else None,
    }


rows = [run_driver("none", "mean")]
base = rows[0]["rounds_to_target"]
for attack in ("sign_flip", "blowup"):
    for agg in ("mean", "trimmed", "median"):
        rows.append(run_driver(attack, agg))
for r in rows:
    print(f"# {r['attack']}/{r['agg']}: rounds={r['rounds_to_target']} "
          f"err_rel={r['final_err_rel']:.3e} "
          f"sub={r['final_suboptimality']:.3e} "
          f"diverged={r['diverged']} guarded={r['guarded_rows']}",
          flush=True)

# deterministic replay: same seeds => bitwise-identical trajectory
a = run_driver("sign_flip", "trimmed")
b = run_driver("sign_flip", "trimmed")
replay_ok = (a["rounds_to_target"] == b["rounds_to_target"]
             and a["x_fingerprint"] == b["x_fingerprint"])

# robust comm-step overhead vs the mean path (the ws impl the loop uses)
rngt = np.random.default_rng(11)
Xt = jnp.asarray(rngt.normal(size=(N, TIME_D)), jnp.float32)
ht = jnp.asarray(rngt.normal(size=(N, TIME_D)), jnp.float32)
slot_t = np.full(N, -1, np.int64)
coh_t = rngt.choice(N, size=C, replace=False)
slot_t[coh_t] = rngt.permutation(C)
slot_t = jnp.asarray(slot_t, jnp.int32)


def timed(spec, s):
    fn = jax.jit(lambda X, h: comm_ws.cyclic_comm(
        X, h, slot_t, C, s, 0.37, impl="ws", robust=spec))
    jax.block_until_ready(fn(Xt, ht))
    best = float("inf")
    for _ in range(3):  # best-of-3: scheduler noise only ever adds time
        t0 = time.perf_counter()
        for _ in range(TIME_ITERS):
            out = fn(Xt, ht)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / TIME_ITERS * 1e6)
    return best


# acceptance overhead is measured at the production comm-engine shape:
# the sparsified uplink (s << c) on TIME_D-wide payloads, where the
# robust combine rides the same s-row owner stacks the masked-sum mean
# already materializes.  The s = c redundancy regime the convergence
# rows run in is reported alongside (sorting c values per coordinate
# vs summing them is intrinsically super-1.5x there — that regime
# trades comm time for Byzantine tolerance by design).
S_PROD = min(4, C)
t_mean = timed(None, S_PROD)
t_trim = timed(("trimmed", 1), S_PROD)
t_med = timed(("median", 0), S_PROD)
overhead = max(t_trim, t_med) / t_mean
t_mean_sc = timed(None, S)
overhead_sc = max(timed(("trimmed", TRIM_K), S),
                  timed(("median", 0), S)) / t_mean_sc

# identity contract: trimmed k=0 == mean bitwise, all four impls
mesh = jax.make_mesh((1, 1), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
spec0 = robust.normalize_robust("trimmed", 0, S)
identity_ok = spec0 is None
for impl, meshed, kw in (("dense", False, {}), ("ws", False, {}),
                         ("pallas", False, {}),
                         ("pallas", True,
                          {"mesh": mesh, "shard_kernels": False})):
    f = lambda rb: jax.jit(lambda X, h: comm_ws.cyclic_comm(
        X, h, slot_t, C, S, 0.37, impl=impl, meshed=meshed, robust=rb,
        **kw))(Xt, ht)
    (xa, ha), (xb, hb) = f(None), f(spec0)
    identity_ok &= bool(
        (np.asarray(xa) == np.asarray(xb)).all()
        and (np.asarray(ha) == np.asarray(hb)).all())

# int8 wire interplay: robust stats on the dequantized values stay at
# quantization scale of the f32-wire robust aggregate
seed_w = wire.round_seed(jax.random.key(5))
xw, _ = jax.jit(lambda X, h: comm_ws.cyclic_comm(
    X, h, slot_t, C, S, 0.37, impl="ws", robust=("trimmed", TRIM_K),
    wire="int8", wire_seed=seed_w))(Xt, ht)
xf, _ = jax.jit(lambda X, h: comm_ws.cyclic_comm(
    X, h, slot_t, C, S, 0.37, impl="ws",
    robust=("trimmed", TRIM_K)))(Xt, ht)
wire_dev = float(jnp.abs(xw - xf).max())

by = {(r["attack"], r["agg"]): r for r in rows}


def ratio(attack, agg):
    r = by[(attack, agg)]["rounds_to_target"]
    return (r / base) if (r and base) else None


def control_stalls(attack):
    r = by[(attack, "mean")]
    return (r["diverged"] or r["rounds_to_target"] is None
            or not np.isfinite(r["final_err_rel"])
            or r["final_err_rel"] >= 10 * TARGET_REL)


out = {
    "rows": rows,
    "target_rel": TARGET_REL,
    "fault_free_rounds": base,
    "ratios": {f"{a}/{g}": ratio(a, g)
               for a in ("sign_flip", "blowup")
               for g in ("trimmed", "median")},
    "mean_control_stalls": {a: control_stalls(a)
                            for a in ("sign_flip", "blowup")},
    "comm_step_us": {"mean": t_mean, "trimmed": t_trim, "median": t_med},
    "robust_overhead_ratio": overhead,
    "robust_overhead_ratio_s_eq_c": overhead_sc,
    "overhead_shape": {"s": S_PROD, "trim_k": 1, "d": TIME_D},
    "identity_bitwise_ok": identity_ok,
    "deterministic_replay_ok": replay_ok,
    "int8_wire_max_dev": wire_dev,
    "acceptance": {"robust_ratio_max": 2.0, "overhead_ratio_max": 1.5,
                   "mean_control_must_stall": True,
                   "identity_bitwise": True, "replay_bitwise": True,
                   "int8_wire_dev_max": 0.25},
    "config": {"n": N, "d": D, "c": C, "s": S, "trim_k": TRIM_K,
               "L": L, "f_byz": F_BYZ, "kappa": KAPPA,
               "target_rel": TARGET_REL, "max_rounds": MAX_ROUNDS,
               "time_d": TIME_D,
               "attack_metric": "rel_sq_dist_to_honest_subset_optimum",
               "byzantine": [int(i) for i in np.flatnonzero(BYZ)]},
}
print(json.dumps(out))
"""


def _bench(smoke: bool) -> dict:
    return child_json(_CODE, what="robust bench", smoke=smoke)


def run(paper_scale: bool = False, smoke: bool = False):
    del paper_scale
    art = _bench(smoke=smoke)
    if not smoke:  # smoke runs must not clobber the measured artifact
        with open(ARTIFACT, "w") as f:
            json.dump(art, f, indent=1)
    rows = []
    for r in art["rows"]:
        tag = f"robust/{r['attack']}/{r['agg']}"
        reached = r["rounds_to_target"]
        rows.append({
            "name": tag,
            "us_per_call": float(reached if reached is not None else -1),
            "derived": (f"rounds_to_target={reached} "
                        f"err_rel={r['final_err_rel']:.2e} "
                        f"diverged={r['diverged']} "
                        f"guarded={r['guarded_rows']}"),
        })
    rows.append({
        "name": "robust/comm_overhead_ratio",
        "us_per_call": round(art["robust_overhead_ratio"], 3),
        "derived": (f"acceptance: <= 1.5x mean comm step at the "
                    f"production uplink {art['overhead_shape']}; "
                    f"mean={art['comm_step_us']['mean']:.0f}us "
                    f"trimmed={art['comm_step_us']['trimmed']:.0f}us "
                    f"median={art['comm_step_us']['median']:.0f}us "
                    f"(s=c redundancy regime: "
                    f"{art['robust_overhead_ratio_s_eq_c']:.2f}x)"),
    })
    ratios = art.get("ratios", {})
    stalls = art.get("mean_control_stalls", {})
    rows.append({
        "name": "robust/acceptance",
        "us_per_call": max(
            [v for v in ratios.values() if v is not None] or [-1.0]),
        "derived": (f"ratios={ratios} mean_stalls={stalls} "
                    f"identity={art.get('identity_bitwise_ok')} "
                    f"replay={art.get('deterministic_replay_ok')} "
                    f"wire_dev={art.get('int8_wire_max_dev'):.3g}"),
    })
    return rows


if __name__ == "__main__":
    for r in run(smoke=os.environ.get("REPRO_BENCH_SMOKE") == "1"):
        print(r)
