"""Fused round engine: the round, not the local step, is the unit of
compiled execution.

The seed driver dispatched one un-donated jit call per local step, blocked
on a host-side sampler between steps, and synced the loss to the host every
round.  Here a whole round runs as donated compiled programs:

  * ``make_round_fn(cfg, tcfg, mesh)`` compiles one donated program per
    round-length *bucket*: ``B`` local steps under ``jax.lax.scan`` followed
    by the comm step behind ``lax.cond``.  A host-sampled geometric length
    ``L`` is decomposed into descending powers of two
    (``round_chunks``), every chunk but the last runs with the comm branch
    off, so across any sequence of rounds at most ``log2(max_L) + 1``
    distinct programs ever compile (the cache is inspectable as
    ``round_fn.cache``).
  * Data is sampled **on device** inside the scan body
    (``repro.data.pipeline.device_sample_batch``) from PRNG keys folded out
    of the scan carry: ``data_step_key(base, t)`` for local step ``t`` and
    ``comm_round_key(base, round)`` for the round's comm step.  Steady-state
    training performs zero host->device transfers.
  * ``run_rounds`` drives multiple rounds with on-device metric
    accumulation: per-round loss / L / comm-float traces are written with
    ``.at[slot]`` updates inside the donated programs and drained to a
    ``MetricLogger`` every ``flush_every`` rounds — the drain is the only
    host sync.
  * **Elastic partial participation** (DESIGN.md §11): at ``c < n`` —
    where cohort rows can vacate hardware (single-device client axis or
    stacked clients; gated default, see ``make_round_fn``) — each chunk
    gathers the round's cohort rows into a compact ``(c, ...)`` state,
    runs its local steps there (O(c·L) compute and gradient memory —
    idle clients do nothing), scatters back, and the comm step's DownCom
    writes only the NEXT round's cohort.  Cohorts come from the round's
    comm key on device (uniform) or a host ``CohortPlan``
    (availability-driven, ``run_rounds(plan=...)``).
  * Both uplinks route through the mask-free comm paths of
    ``repro.dist.comm_ws`` (``tcfg.comm_impl``, default auto: sparse fused
    uplink off-TPU, flat-workspace Pallas kernels on TPU — DESIGN.md §9),
    so the fused round program's comm step never materializes a dense
    ownership mask or scans all ``n`` client rows for the UpCom.  With
    ``comm_impl="pallas"`` the meshed comm step is the shard-resident
    engine (§10): ``make_comm_step`` hands the mesh and the stacked state
    specs to ``comm_ws``, which shard_maps the kernels over the dp axes
    inside the same donated round program — per-shard uplinks, one
    d-sized psum of the partials, behind the same ``lax.cond``.

  * **Pipelined rounds under bounded staleness** (DESIGN.md §14): the
    bulk-synchronous barrier above pays the slowest cohort member's
    straggler tail every round.  ``make_pipelined_round_fn`` splits the
    round into separately donated *stage* (cohort gather + ``L`` local
    steps into a compact ping-pong payload buffer) and *commit* (scatter
    + UpCom/h-update/DownCom) programs, and ``run_rounds_pipelined``
    keeps up to ``τ`` rounds in flight: round ``t``'s commit is deferred
    to pipeline slot ``t+τ`` so its stragglers get ``τ`` rounds of
    wall-clock grace (late uplinks admitted into the deferred rebuild, or
    demoted to dropped through PR 6's ``arrived``-mask survivor
    aggregation), the DownCom prefetches ``x_bar`` to the cohort that
    joins next (global-round indexed, known at dispatch time), and a
    host-side simulated clock driven by ``FaultPlan``/``EmpiricalDelays``
    latency draws prices the overlap.  In-flight cohorts are pairwise
    disjoint (a client mid-round cannot join a new cohort), which is what
    makes the deferred commit exact: nothing touches a staged cohort's
    rows between its gather and its commit.  ``τ=0`` runs the identical
    op sequence as the synchronous engine (stage, then commit
    immediately) — equivalence-tested to ≤1e-6 for both uplinks.

The key-derivation helpers are public so the per-step reference path (and
the equivalence tests) can replay the exact same schedule.  See DESIGN.md
§8.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import spans
from repro.dist import sharding, tamuna_dp
from repro.dist.tamuna_dp import _as_key
from repro.models.transformer import ModelConfig

__all__ = [
    "RoundCarry",
    "round_chunks",
    "data_step_key",
    "comm_round_key",
    "default_elastic",
    "make_round_fn",
    "make_fused_round",
    "init_carry",
    "run_rounds",
    "make_pipelined_round_fn",
    "run_rounds_pipelined",
    "pipeline_checkpoint_save",
    "pipeline_checkpoint_restore",
    "pipeline_latest_step",
]


def default_elastic(n: int, c: int, dp_total: int) -> bool:
    """Whether the engine gathers by default: only where cohort rows can
    actually vacate hardware — a single-device client axis, or stacked
    clients (``n > dp``) whose cohort divides the dp extent.  With one
    client per device the compact ``(c, ...)`` state cannot shard over
    dp: GSPMD replicates the cohort's gradient work onto every shard and
    remats the gather (measured ~500x round bytes on the pod16x16
    dry-run — DESIGN.md §11, EXPERIMENTS §Perf 9).  Shared by
    ``make_round_fn``, ``make_fused_round``, and the per-step trainer."""
    return c < n and (dp_total == 1 or (n > dp_total and c % dp_total == 0))

# Batch sampler contract: ``sample_batch(data, key) -> {"tokens": ..., ...}``
# where ``data`` is a device-resident pytree passed alongside the donated
# carry as a read-only argument (uploaded once, never baked into programs,
# never donated — the caller's handle stays valid).
SampleFn = Callable[[Any, jax.Array], Dict[str, jax.Array]]

TRACE_KEYS = ("loss_sum", "steps", "up_floats", "down_floats",
              "up_bytes", "down_bytes")
# extra per-round device traces of the fault-tolerant driver (present in
# the carry only when ``init_carry(robust_n=...)`` > 0): arrivals = cohort
# members whose uplink was aggregated, corrupted = members zeroed by the
# payload guard, bad = the (flush_every, n) guard mask the quarantine
# feedback reads
FAULT_TRACE_KEYS = ("arrivals", "corrupted", "bad")
ROUND_POLICIES = ("wait_all", "quorum", "deadline")


class RoundCarry(NamedTuple):
    """Everything a round program owns; donated wholesale every call.  The
    pipeline tables stay OUTSIDE the carry (a separate, read-only argument)
    so donation never invalidates the caller's ``device_data()`` handle."""

    state: tamuna_dp.DistTamunaState
    t: jax.Array  # int32 scalar: total local steps taken so far
    data_key: jax.Array  # (2,) uint32 base key-data for data sampling
    comm_key: jax.Array  # (2,) uint32 base key-data for comm steps
    traces: Dict[str, jax.Array]  # per-round device traces, slot-indexed


def round_chunks(L: int, max_L: int = 16) -> list:
    """Decompose a round length into descending power-of-two chunks.

    ``sum(round_chunks(L)) == min(L, max_L)`` exactly, and the set of chunk
    sizes that can ever appear is ``{1, 2, ..., 2^floor(log2(max_L))}`` —
    the compile cache is bounded by ``log2(max_L) + 1`` programs.
    """
    L = max(1, min(int(L), int(max_L)))
    return [1 << b for b in range(L.bit_length() - 1, -1, -1)
            if (L >> b) & 1]


def data_step_key(base: jax.Array, t) -> jax.Array:
    """Key for the batch of global local-step ``t`` (typed PRNG key)."""
    return jax.random.fold_in(_as_key(base), t)


def comm_round_key(base: jax.Array, rnd) -> jax.Array:
    """Key for the comm step ending round ``rnd`` (``state.round``)."""
    return jax.random.fold_in(_as_key(base), rnd)


def _zero_traces(flush_every: int, robust_n: int = 0,
                 coverage: bool = False,
                 anomaly: bool = False) -> Dict[str, jax.Array]:
    traces = {
        "loss_sum": jnp.zeros((flush_every,), jnp.float32),
        "steps": jnp.zeros((flush_every,), jnp.int32),
        "up_floats": jnp.zeros((flush_every,), jnp.float32),
        "down_floats": jnp.zeros((flush_every,), jnp.float32),
        "up_bytes": jnp.zeros((flush_every,), jnp.float32),
        "down_bytes": jnp.zeros((flush_every,), jnp.float32),
    }
    if robust_n:
        traces["arrivals"] = jnp.zeros((flush_every,), jnp.int32)
        traces["corrupted"] = jnp.zeros((flush_every,), jnp.int32)
        traces["bad"] = jnp.zeros((flush_every, robust_n), bool)
        if coverage:
            # per-round count of coordinates the survivor-aware UpCom
            # left uncovered (no arrived owner) — the staleness/quality
            # signal of the pipelined driver (DESIGN.md §14)
            traces["uncovered"] = jnp.zeros((flush_every,), jnp.int32)
        if anomaly:
            # per-client distance-to-robust-aggregate scores
            # (robust.anomaly_scores) feeding the EWMA reputation that
            # drives escalating quarantine windows (DESIGN.md §15)
            traces["anomaly"] = jnp.zeros((flush_every, robust_n),
                                          jnp.float32)
    return traces


def _scan_local(local, sample_batch: SampleFn, state, data, dkey, t, B: int,
                clients=None):
    """``B`` local steps under ``lax.scan``, batches sampled on device from
    ``fold_in(dkey, t)``; returns (state, t, summed loss).  ``clients``
    restricts the sample to the round's cohort rows (the state is then the
    compact ``(c, ...)`` gather and per-client streams stay keyed by the
    ACTUAL client ids, invariant to who else participates)."""

    def body(inner, _):
        st, tt, acc = inner
        key = jax.random.fold_in(dkey, tt)
        batch = (sample_batch(data, key) if clients is None
                 else sample_batch(data, key, clients=clients))
        st, m = local(st, **batch)
        return (st, tt + 1, acc + m["loss"]), None

    # the scope holds the whole loop (sampling and its bookkeeping too),
    # so every op of the scan body carries it
    with jax.named_scope(spans.LOCAL_STEP):
        (state, t, loss_sum), _ = jax.lax.scan(
            body, (state, t, jnp.float32(0.0)), None, length=B
        )
    return state, t, loss_sum


def make_round_fn(
    cfg: ModelConfig,
    tcfg: tamuna_dp.DistTamunaConfig,
    mesh,
    *,
    sample_batch: SampleFn,
    max_L: int = 16,
    n: Optional[int] = None,
    elastic: Optional[bool] = None,
):
    """Build ``round_fn(carry, data, L, slot, cohort=None, down=None) ->
    carry`` running one round.

    ``data`` is the device-resident pipeline table pytree (read-only, never
    donated); ``L`` is the (host-sampled) number of local steps; ``slot`` is
    the trace row this round writes (``global_round % flush_every``).  The
    callable exposes ``round_fn.cache`` (bucket -> compiled program),
    ``round_fn.lowered()`` (bucket -> its lowering), ``round_fn.max_L``,
    ``round_fn.n``, ``round_fn.c``, ``round_fn.elastic``,
    ``round_fn.route_coords`` (the comm step's coordinates a row per
    route, ``comm_ws.route_coords``).

    **Elastic partial participation** (default whenever ``tcfg.c < n``,
    DESIGN.md §11): every chunk gathers the round's ``c`` cohort rows into
    a compact ``(c, ...)`` state, runs its local steps there (batches
    sampled for cohort clients only), and scatters back — local compute
    and gradient memory are O(c·L), idle clients do nothing.  The cohort
    is derived on device from the round's comm key
    (``tamuna_dp.round_cohort(comm_round_key(base, round), n, c)`` — every
    chunk of a round sees the same ``state.round``, hence the same
    cohort), unless the caller passes an explicit ``cohort`` (host plans:
    ``repro.dist.cohort.CohortPlan`` for availability-driven sampling).
    The comm step's DownCom then targets only the NEXT round's cohort
    (``down``; device-derived symmetrically when None), so clients sitting
    out a round are bitwise untouched.

    The default only goes elastic where cohort rows can actually vacate
    hardware: a single-device client axis, or stacked clients
    (``n > dp``) whose cohort divides the dp extent.  With one client per
    device (``n == dp``) the compact ``(c, ...)`` state cannot shard over
    the dp axis — GSPMD replicates the cohort's gradient work onto every
    shard and remats the gather (measured on the pod16x16 dry-run:
    ~500x the round's memory traffic, EXPERIMENTS §Perf 9) — so those
    placements keep the all-rows body, whose DownCom must broadcast
    (every row trains, every row re-syncs to ``x_bar``).  ``elastic=``
    overrides the default either way.
    """
    n = n or sharding.n_clients(mesh)
    c = tcfg.c
    if elastic is None:
        elastic = default_elastic(n, c, sharding.n_clients(mesh))
    local = tamuna_dp.make_local_step(cfg, tcfg)
    comm = tamuna_dp.make_comm_step(cfg, tcfg, mesh, n=n)

    def chunk_fn(B: int, carry: RoundCarry, data, do_comm, slot,
                 cohort, down, arrived=None, corrupt=None, byz=None, *,
                 correct: bool = True, guard: bool = False,
                 guard_mode: str = "nonfinite",
                 corrupt_mode: str = "nan", blowup: float = 1e8,
                 guard_max_abs: Optional[float] = None,
                 adversary: str = "none", byz_scale: float = -10.0,
                 byz_z: float = 1.5) -> RoundCarry:
        state, t, dk, ck, traces = carry
        if elastic:
            if cohort is None:
                cohort = tamuna_dp.round_cohort(
                    comm_round_key(ck, state.round), n, c
                )
            if down is None:
                down = tamuna_dp.member_mask(
                    tamuna_dp.round_cohort(
                        comm_round_key(ck, state.round + 1), n, c
                    ), n,
                )
            compact = tamuna_dp.gather_cohort(state, cohort)
            compact, t, loss_sum = _scan_local(
                local, sample_batch, compact, data, _as_key(dk), t, B,
                clients=cohort,
            )
            state = tamuna_dp.scatter_cohort(state, compact, cohort)
        else:
            # all-rows body: every row trains, so every row must re-sync
            # to x_bar at comm time — a masked DownCom would leave
            # non-cohort rows on their (discarded) local trajectories
            down = None
            state, t, loss_sum = _scan_local(
                local, sample_batch, state, data, _as_key(dk), t, B
            )

        if arrived is None:
            @jax.named_scope(spans.COMM_STEP)
            def with_comm(st):
                ckey = comm_round_key(ck, st.round)
                return comm(st, jax.random.key_data(ckey), cohort=cohort,
                            down=down)

            state = jax.lax.cond(do_comm, with_comm, lambda st: st, state)
            fault_out = None
        else:
            # the fault-tolerant comm branch (DESIGN.md §12/§15):
            # corruption and adversarial payloads are injected into the
            # would-be uplink, the payload guard demotes nonfinite (and,
            # in adaptive mode, magnitude-outlier) members to non-arrived
            # (and zeroes their rows so leftover garbage can't reach a
            # later loss), and the comm step aggregates survivors only
            from repro.dist import faults as faults_mod
            from repro.dist import robust as robust_mod

            member = jnp.zeros((n,), bool).at[cohort].set(True)
            want_anom = "anomaly" in traces

            @jax.named_scope(spans.COMM_STEP)
            def with_comm(st):
                ckey = comm_round_key(ck, st.round)
                stx = st
                if corrupt is not None:
                    stx = stx._replace(x=faults_mod.corrupt_rows(
                        stx.x, corrupt, corrupt_mode, blowup
                    ))
                arr = arrived & member
                if byz is not None:
                    # Byzantine rows only matter if they arrive; the
                    # inlier attack colludes against the arrived honest
                    stx = stx._replace(x=faults_mod.adversarial_rows(
                        stx.x, byz & arr, arr & ~byz, adversary,
                        byz_scale=byz_scale, byz_z=byz_z,
                    ))
                if guard:
                    bad = faults_mod.nonfinite_clients(
                        stx.x, guard_max_abs
                    ) & member
                    if guard_mode == "adaptive":
                        bad = bad | (robust_mod.magnitude_outliers(
                            stx.x, arr & ~bad
                        ) & member)
                    arr = arr & ~bad
                    stx = stx._replace(x=jax.tree.map(
                        lambda a: jnp.where(
                            bad.reshape((n,) + (1,) * (a.ndim - 1)),
                            jnp.zeros((), a.dtype), a,
                        ),
                        stx.x,
                    ))
                else:
                    bad = jnp.zeros((n,), bool)
                anom = (robust_mod.anomaly_scores(stx.x, arr)
                        if want_anom else jnp.zeros((n,), jnp.float32))
                st2 = comm(stx, jax.random.key_data(ckey), cohort=cohort,
                           down=down, arrived=arr, correct=correct)
                return st2, arr.sum().astype(jnp.int32), bad, anom

            def no_comm(st):
                return (st, jnp.int32(0), jnp.zeros((n,), bool),
                        jnp.zeros((n,), jnp.float32))

            state, *fault_out = jax.lax.cond(
                do_comm, with_comm, no_comm, state
            )
        with jax.named_scope(spans.TRACES):
            out_traces = {
                "loss_sum": traces["loss_sum"].at[slot].add(loss_sum),
                "steps": traces["steps"].at[slot].add(B),
                "up_floats": traces["up_floats"].at[slot].set(
                    state.up_floats
                ),
                "down_floats": traces["down_floats"].at[slot].set(
                    state.down_floats
                ),
                "up_bytes": traces["up_bytes"].at[slot].set(state.up_bytes),
                "down_bytes": traces["down_bytes"].at[slot].set(
                    state.down_bytes
                ),
            }
            if fault_out is not None:
                arr_cnt, badm, anom = fault_out
                out_traces.update({
                    "arrivals": traces["arrivals"].at[slot].set(arr_cnt),
                    "corrupted": traces["corrupted"].at[slot].set(
                        badm.sum().astype(jnp.int32)
                    ),
                    "bad": traces["bad"].at[slot].set(badm),
                })
                if want_anom:
                    out_traces["anomaly"] = traces["anomaly"].at[slot].set(
                        anom
                    )
        return RoundCarry(state, t, dk, ck, out_traces)

    cache: Dict[Any, Callable] = {}
    signatures: Dict[Any, Any] = {}  # key -> abstract args of first call

    def program(B: int, with_plan: bool, fkey=None):
        key = (B, with_plan, fkey)
        if key not in cache:
            if fkey is None:
                cache[key] = jax.jit(
                    partial(chunk_fn, B), donate_argnums=(0,)
                )
            else:
                (correct, guard, gmode, mode, blowup, gmax,
                 adversary, bscale, bz) = fkey
                cache[key] = jax.jit(
                    partial(chunk_fn, B, correct=correct, guard=guard,
                            guard_mode=gmode, corrupt_mode=mode,
                            blowup=blowup, guard_max_abs=gmax,
                            adversary=adversary, byz_scale=bscale,
                            byz_z=bz),
                    donate_argnums=(0,),
                )
        return cache[key]

    def round_fn(carry: RoundCarry, data, L: int, slot,
                 cohort=None, down=None, arrived=None, corrupt=None,
                 byz=None, correct: bool = True, guard: bool = False,
                 guard_mode: str = "nonfinite",
                 corrupt_mode: str = "nan", blowup: float = 1e8,
                 guard_max_abs: Optional[float] = None,
                 adversary: str = "none", byz_scale: float = -10.0,
                 byz_z: float = 1.5) -> RoundCarry:
        chunks = round_chunks(L, max_L)
        slot = jnp.asarray(slot, jnp.int32)
        with_plan = cohort is not None
        if with_plan and down is None:
            # a host plan must pin the DownCom too: without it the engine
            # would derive a (different) uniform next cohort on device
            raise ValueError("explicit cohort needs an explicit down mask")
        if arrived is None:
            if corrupt is not None:
                raise ValueError("corrupt mask needs an arrived mask")
            for i, B in enumerate(chunks):
                do_comm = jnp.asarray(i == len(chunks) - 1)
                carry = run((B, with_plan, None), carry, data, do_comm,
                            slot, cohort, down)
            return carry
        # fault-tolerant rounds carry the arrival mask into every chunk
        # (only the comm chunk consumes it) plus the static fault config
        # in the compile key; the carry must have been built with
        # init_carry(robust_n=n)
        if not with_plan:
            raise ValueError("fault injection needs an explicit cohort "
                             "(resolve it host-side, see run_rounds)")
        fkey = (bool(correct), bool(guard), str(guard_mode),
                str(corrupt_mode), float(blowup),
                None if guard_max_abs is None else float(guard_max_abs),
                str(adversary), float(byz_scale), float(byz_z))
        arrived = jnp.asarray(arrived).astype(bool)
        if corrupt is not None:
            corrupt = jnp.asarray(corrupt).astype(bool)
        if byz is not None:
            byz = jnp.asarray(byz).astype(bool)
        for i, B in enumerate(chunks):
            do_comm = jnp.asarray(i == len(chunks) - 1)
            carry = run((B, with_plan, fkey), carry, data, do_comm, slot,
                        cohort, down, arrived, corrupt, byz)
        return carry

    def run(key, *args):
        if key in signatures or any(
                isinstance(a, jax.core.Tracer) for a in jax.tree.leaves(args)):
            return program(*key)(*args)
        # uncommitted arrays (jnp scalars) go wherever jit puts them:
        # no sharding, or the lowering would pin them to one device
        signatures[key] = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=a.sharding if getattr(a, "committed", False)
                else None,
                weak_type=getattr(a, "weak_type", False)),
            args)
        # the first call traces, lowers and compiles (or loads) the program
        B, with_plan, fkey = key
        name = (f"{spans.COMPILE}{B}" + (".plan" if with_plan else "")
                + (".faults" if fkey is not None else ""))
        with jax.profiler.TraceAnnotation(name):
            return program(*key)(*args)

    def lowered() -> Dict[Any, Any]:
        """``{bucket: jax.stages.Lowered}``: every program this round_fn
        has run, lowered again from its first call's shapes and
        shardings (no device memory) — what the compiler was handed."""
        return {k: cache[k].lower(*sig) for k, sig in signatures.items()}

    round_fn.cache = cache
    round_fn.lowered = lowered
    round_fn.max_L = max_L
    round_fn.n = n
    round_fn.c = c
    round_fn.elastic = elastic
    # (a comm step that a caller wraps need not carry the counter)
    round_fn.route_coords = getattr(comm, "route_coords", None)
    return round_fn


def make_fused_round(
    cfg: ModelConfig,
    tcfg: tamuna_dp.DistTamunaConfig,
    mesh,
    *,
    sample_batch: SampleFn,
    L: int,
    n: Optional[int] = None,
    elastic: Optional[bool] = None,
):
    """Static-``L`` fused round ``fn(state, key_data, data) -> (state, loss)``
    with an unconditional comm step — the shape the dry-run lowers so the
    roofline artifacts see the scanned round, and the bench times.  At
    ``c < n`` this is the elastic round (cohort gather -> O(c·L) local
    compute -> scatter -> comm; ``elastic=False`` forces the all-rows
    contrast), with the cohort derived in-program from the comm key, so
    the lowered HLO's gradient FLOPs scale with ``c`` — the artifact the
    idle-clients-do-no-work regression checks.  Default elasticity is
    ``default_elastic`` (gathering is a pessimization when cohort rows
    cannot vacate hardware)."""
    n = n or sharding.n_clients(mesh)
    c = tcfg.c
    if elastic is None:
        elastic = default_elastic(n, c, sharding.n_clients(mesh))
    local = tamuna_dp.make_local_step(cfg, tcfg)
    comm = tamuna_dp.make_comm_step(cfg, tcfg, mesh, n=n)

    def fn(state, key_data, data):
        kd, kc = jax.random.split(_as_key(key_data))
        t0 = jnp.zeros((), jnp.int32)
        ckey = comm_round_key(jax.random.key_data(kc), state.round)
        if elastic:
            cohort = tamuna_dp.round_cohort(ckey, n, c)
            compact = tamuna_dp.gather_cohort(state, cohort)
            compact, _, loss_sum = _scan_local(
                local, sample_batch, compact, data, kd, t0, L,
                clients=cohort,
            )
            state = tamuna_dp.scatter_cohort(state, compact, cohort)
            # DownCom broadcasts here (down=None): each call of this
            # static round derives cohorts from ITS OWN key, so a mask
            # aimed at "this key's next cohort" would not match the
            # cohort the NEXT call actually draws — a client could then
            # enter a round without ever receiving x_bar.  The chunked
            # engine (make_round_fn) can target the true next cohort
            # because its comm key base is fixed in the carry.
            state = comm(state, jax.random.key_data(ckey), cohort=cohort)
        else:
            state, _, loss_sum = _scan_local(
                local, sample_batch, state, data, kd, t0, L,
            )
            state = comm(state, jax.random.key_data(ckey))
        return state, loss_sum / L

    return fn


def init_carry(
    state: tamuna_dp.DistTamunaState,
    key: jax.Array,
    flush_every: int,
    robust_n: int = 0,
    coverage: bool = False,
    anomaly: bool = False,
) -> RoundCarry:
    kd, kc = jax.random.split(_as_key(key))
    return RoundCarry(
        state=state,
        t=jnp.zeros((), jnp.int32),
        data_key=jax.random.key_data(kd),
        comm_key=jax.random.key_data(kc),
        traces=_zero_traces(flush_every, robust_n, coverage, anomaly),
    )


def _make_fault_resolver(faults, *, n: int, policy: str, q, max_retries: int,
                         backoff0: float, deadline, host_cohort):
    """Host-side survivor resolution shared by the synchronous and the
    τ=0 pipelined drivers (identical retry/backoff semantics, so the two
    admit bit-identical arrival masks).  ``resolve(g)`` returns a dict
    with cohort/member/arrived/corrupt masks plus retry accounting;
    results are memoized in ``resolve.cache`` (the quarantine feedback
    purges entries past the detection round)."""
    resolved: Dict[int, Any] = {}

    def resolve(g: int):
        import numpy as np

        got = resolved.get(g)
        if got is not None:
            return got
        attempt, backoff, quorum_miss = 0, 0.0, 0
        while True:
            cohort = host_cohort(g, attempt)
            member = np.zeros(n, bool)
            member[cohort] = True
            arrived = member & ~faults.drops(g, attempt)
            if policy == "deadline":
                arrived &= faults.delays(g, attempt) <= deadline
            if (policy == "quorum" and int(arrived.sum()) < q
                    and attempt < max_retries):
                quorum_miss += 1
                backoff += backoff0 * (2.0 ** attempt)
                attempt += 1
                continue
            break
        res = {
            "cohort": cohort,
            "member": member,
            "arrived": arrived,
            "corrupt": faults.corrupts(g, attempt) & member,
            "retries": attempt,
            "backoff": backoff,
            "quorum_miss": quorum_miss,
        }
        resolved[g] = res
        return res

    resolve.cache = resolved
    return resolve


def run_rounds(
    state: tamuna_dp.DistTamunaState,
    *,
    round_fn,
    data: Any,
    key: jax.Array,
    rounds: int,
    rng,
    p: float,
    flush_every: int = 10,
    logger=None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    max_L: Optional[int] = None,
    plan=None,
    faults=None,
    policy: str = "wait_all",
    quorum: Optional[int] = None,
    max_retries: int = 3,
    backoff0: float = 1.0,
    deadline: Optional[float] = None,
    quarantine_rounds: int = 0,
    guard: Optional[bool] = None,
    guard_max_abs: Optional[float] = None,
    guard_mode: Optional[str] = None,
    reputation=None,
) -> Tuple[tamuna_dp.DistTamunaState, Dict[str, Any]]:
    """Multi-round driver: geometric ``L`` per round (host ``rng``), fused
    rounds on device, metrics drained every ``flush_every`` rounds.

    Steady state does no per-local-step host->device transfer and no
    per-round host sync; the only blocking points are the trace drain (once
    per flush) and checkpoint saves.  Returns the final state and the last
    drained per-round metrics row.

    ``plan`` (a ``repro.dist.cohort.CohortPlan``) drives *non-uniform*
    cohort sampling — availability models, latency weighting — from the
    host: the plan is indexed by the GLOBAL round counter (``state.round``
    at entry plus the loop index), so a restored checkpoint replays the
    identical schedule; per round it uploads the tiny ``(c,)`` cohort and
    ``(n,)`` DownCom mask.  ``plan=None`` (the default) keeps cohort
    selection on device, derived from the comm key (uniform).

    ``faults`` (a ``repro.dist.faults.FaultPlan``) turns on the
    fault-tolerant round path (DESIGN.md §12).  Per round the plan's
    deterministic draws decide which cohort members drop their uplink,
    which corrupt their payload, and each member's latency; the driver
    resolves the round's *survivors* host-side (the draws are replayable,
    so a failed attempt never executes on device) and runs exactly one
    device round per global round with the arrival mask:

      wait_all  accept whatever arrives, but aggregate with the legacy
                1/s semantics (``correct=False``) — the biased control.
                Under a zero-fault plan this passes ``arrived=None`` and
                is bitwise identical to the fault-free driver.
      quorum    require ``quorum`` arrivals (default ``c // 2 + 1``);
                on a miss, resample the cohort (``plan.cohort(g, attempt)``
                or the attempt-folded comm key) and redraw faults, up to
                ``max_retries`` times with capped exponential backoff
                (``backoff0 * 2**attempt`` simulated seconds, accounted in
                the metrics, never slept).  Survivor-aware aggregation
                (``correct=True``).
      deadline  admit only members whose drawn latency is ``<= deadline``
                (and that didn't drop); survivor-aware aggregation.

    ``guard`` (default: on iff the fault model corrupts payloads or
    carries a Byzantine adversary) enables the payload guard: flagged
    members are demoted to non-arrived before aggregation and, when
    ``quarantine_rounds > 0`` and a ``plan`` is given, quarantined from
    selection for that many rounds starting at detection + 2 (the next
    round's cohort is already committed as this round's DownCom target).
    ``guard_mode`` picks the detector: ``"nonfinite"`` (NaN/Inf rows
    only) or ``"adaptive"`` (nonfinite plus the median + k·MAD payload
    norm outlier band of ``robust.magnitude_outliers``).  The default is
    adaptive whenever the fault model can emit FINITE garbage that the
    nonfinite check waves through — ``corrupt_mode="blowup"`` with no
    ``guard_max_abs``, or any adversary model (DESIGN.md §15).

    ``reputation`` (``True`` or a ``robust.Reputation``; needs ``plan``
    and ``faults``) turns on the anomaly feedback loop: each round's
    per-client distance-to-robust-aggregate scores
    (``robust.anomaly_scores``, traced on device) feed an EWMA; clients
    whose EWMA crosses the threshold are quarantined for escalating
    windows (``base_rounds * 2**strikes``).  Pass a ``Reputation``
    restored via ``from_state_dict`` to resume the schedule bit-exactly.
    """
    # never sample past the engine's bucket cap: round_fn silently clamps
    # executed steps to its own max_L, so a larger caller cap would desync
    # the host-side L from the executed count
    engine_cap = getattr(round_fn, "max_L", None)
    max_L = max_L or engine_cap or 16
    if engine_cap:
        max_L = min(max_L, engine_cap)
    flush_every = max(1, min(flush_every, rounds))

    import numpy as np

    n = getattr(round_fn, "n", None)
    c = getattr(round_fn, "c", None)
    if policy not in ROUND_POLICIES:
        raise ValueError(f"unknown policy {policy!r}; pick from "
                         f"{ROUND_POLICIES}")
    adversarial = faults is not None and faults.model.adversarial
    if guard is None:
        guard = faults is not None and (faults.model.p_corrupt > 0
                                        or adversarial)
    if guard_mode is None:
        # ISSUE 9 fix: the nonfinite check admits FINITE corruption —
        # blowup rows (1e8-scaled, faults.py corrupt_rows) and
        # adversarial payloads pass it whenever guard_max_abs is unset,
        # so those models default to the adaptive magnitude guard
        guard_mode = ("adaptive" if bool(guard) and guard_max_abs is None
                      and faults is not None
                      and (adversarial
                           or (faults.model.p_corrupt > 0
                               and faults.model.corrupt_mode == "blowup"))
                      else "nonfinite")
    if guard_mode not in ("nonfinite", "adaptive"):
        raise ValueError(f"unknown guard_mode {guard_mode!r}; pick "
                         "'nonfinite' or 'adaptive'")
    faulted = faults is not None and (
        not faults.is_zero or policy != "wait_all"
        or quarantine_rounds > 0 or bool(guard)
    )
    if faults is None and (policy != "wait_all" or quarantine_rounds > 0):
        raise ValueError("round policies and quarantine need a fault plan")
    if policy == "deadline" and deadline is None:
        raise ValueError("deadline policy needs a deadline (seconds)")
    if quarantine_rounds > 0 and plan is None:
        raise ValueError("quarantine needs a CohortPlan to feed back into")
    if faulted:
        if n is None or c is None:
            raise ValueError("fault-tolerant rounds need a round_fn built "
                             "by make_round_fn (n and c attributes)")
        if faults.n != n:
            raise ValueError(f"fault plan covers {faults.n} clients, "
                             f"round_fn has n={n}")
    if plan is not None and getattr(plan, "weighted", False):
        import warnings

        # known bias, documented in DESIGN.md §11: aggregation never
        # reweights by 1/(n p_i), so non-uniform selection pulls the
        # fixed point toward frequently-sampled clients (full fix is a
        # future PR — this warning pins the gap)
        warnings.warn(
            "CohortPlan has non-uniform selection weights but run_rounds "
            "aggregates without 1/(n p_i) importance reweighting; the "
            "fixed point is biased toward frequently-sampled clients "
            "(DESIGN.md §11)",
            UserWarning, stacklevel=2,
        )
    rep = None
    if reputation is not None and reputation is not False:
        if plan is None or faults is None or not faulted:
            raise ValueError("reputation feedback needs a CohortPlan and "
                             "a fault plan")
        from repro.dist import robust as robust_mod

        rep = (reputation
               if isinstance(reputation, robust_mod.Reputation)
               else robust_mod.Reputation(n))
        if rep.n != n:
            raise ValueError(f"reputation covers {rep.n} clients, "
                             f"round_fn has n={n}")

    start_round = int(state.round) if (plan is not None or faulted) else 0
    with jax.profiler.TraceAnnotation(spans.INIT_CARRY):
        carry = init_carry(state, key, flush_every,
                           robust_n=n if faulted else 0,
                           anomaly=rep is not None)
    q = quorum if quorum is not None else (c // 2 + 1 if c else None)
    byz_mask = (jnp.asarray(faults.byzantine) if faulted and adversarial
                else None)

    if faulted and plan is None:
        # replay the engine's on-device uniform cohorts host-side so the
        # arrival mask lines up with the rows the round actually trains
        ck0 = np.asarray(jax.device_get(carry.comm_key))

    def host_cohort(g: int, attempt: int = 0) -> np.ndarray:
        if plan is not None:
            return np.asarray(plan.cohort(g, attempt))
        ckey = comm_round_key(jnp.asarray(ck0), g)
        if attempt > 0:
            ckey = jax.random.fold_in(ckey, attempt)
        return np.asarray(jax.device_get(
            tamuna_dp.round_cohort(ckey, n, c)
        ))

    resolve = (_make_fault_resolver(
        faults, n=n, policy=policy, q=q, max_retries=max_retries,
        backoff0=backoff0, deadline=deadline, host_cohort=host_cohort,
    ) if faulted else None)

    pending = []  # global round indices awaiting drain
    fmeta = []  # per-pending-round host-side fault accounting
    total_steps = 0
    last: Dict[str, Any] = {}

    def one_round(carry, r: int, slot: int):
        """Round ``r``: its L draw, its cohort and fault resolution
        (host), its program calls; returns the carry."""
        L = tamuna_dp.sample_round_length(rng, p, max_L=max_L)
        g = start_round + r
        if faulted:
            res = resolve(g)
            nxt = resolve(g + 1)
            carry = round_fn(
                carry, data, L, slot,
                cohort=jnp.asarray(res["cohort"], jnp.int32),
                down=jnp.asarray(nxt["member"]),
                arrived=jnp.asarray(res["arrived"]),
                corrupt=(jnp.asarray(res["corrupt"])
                         if faults.model.p_corrupt > 0 else None),
                byz=byz_mask,
                correct=(policy != "wait_all"),
                guard=bool(guard),
                guard_mode=guard_mode,
                corrupt_mode=faults.model.corrupt_mode,
                blowup=faults.model.blowup,
                guard_max_abs=guard_max_abs,
                adversary=faults.model.adversary,
                byz_scale=faults.model.byz_scale,
                byz_z=faults.model.byz_z,
            )
            fmeta.append({
                "retries": res["retries"],
                "backoff_s": res["backoff"],
                "quorum_miss": res["quorum_miss"],
                "round_latency_s": float(
                    faults.delays(g, res["retries"])[res["arrived"]].max()
                    if res["arrived"].any() else 0.0
                ) + res["backoff"],
            })
            if quarantine_rounds > 0:
                # drain this round's guard verdict NOW: quarantine must
                # land before round g+2's cohort is resolved
                bad = np.asarray(
                    jax.device_get(carry.traces["bad"][slot])
                )
                if bad.any():
                    ids = np.where(bad)[0]
                    plan.quarantine(ids, g + 2, g + 1 + quarantine_rounds)
                    for k in [k for k in resolve.cache if k >= g + 2]:
                        del resolve.cache[k]
            if rep is not None:
                # same timing constraint as the guard feedback: the EWMA
                # verdict must land before round g+2's cohort resolves
                anom = np.asarray(
                    jax.device_get(carry.traces["anomaly"][slot])
                )
                badr = np.asarray(
                    jax.device_get(carry.traces["bad"][slot])
                )
                # guard-demoted rows were zeroed on device — their score
                # is a meaningless 0, so keep them out of the EWMA
                wins = rep.update(anom, res["arrived"] & ~badr)
                if wins:
                    for cid, w in wins:
                        plan.quarantine([cid], g + 2, g + 1 + w)
                    for k in [k for k in resolve.cache if k >= g + 2]:
                        del resolve.cache[k]
        elif plan is not None:
            carry = round_fn(
                carry, data, L, slot,
                cohort=jnp.asarray(plan.cohort(g), jnp.int32),
                down=jnp.asarray(plan.member_mask(g + 1)),
            )
        else:
            carry = round_fn(carry, data, L, slot)
        return carry

    for r in range(rounds):
        with jax.profiler.TraceAnnotation(spans.ROUND):
            carry = one_round(carry, r, len(pending))
        pending.append(r)
        if len(pending) == flush_every or r == rounds - 1:
            with jax.profiler.TraceAnnotation(spans.DRAIN):
                tr = jax.device_get(carry.traces)  # the only host sync
            with jax.profiler.TraceAnnotation(spans.FLUSH):
                for i, gr in enumerate(pending):
                    # device truth, not host L
                    executed = int(tr["steps"][i])
                    total_steps += executed
                    last = {
                        "round": gr,
                        "L": executed,
                        "loss": (float(tr["loss_sum"][i])
                                 / max(executed, 1)),
                        "local_steps": total_steps,
                        "up_floats": float(tr["up_floats"][i]),
                        "down_floats": float(tr["down_floats"][i]),
                        "up_bytes": float(tr["up_bytes"][i]),
                        "down_bytes": float(tr["down_bytes"][i]),
                    }
                    if faulted:
                        last.update({
                            "arrivals": int(tr["arrivals"][i]),
                            "corrupted": int(tr["corrupted"][i]),
                            **fmeta[i],
                        })
                        if rep is not None:
                            last["anomaly_max"] = float(
                                tr["anomaly"][i].max())
                    if logger is not None:
                        logger.log(gr, last)
                pending = []
                fmeta = []
                carry = carry._replace(
                    traces=_zero_traces(flush_every, n if faulted else 0,
                                        anomaly=rep is not None)
                )
        if (checkpoint_dir and checkpoint_every
                and (r + 1) % checkpoint_every == 0):
            from repro import checkpoint

            with jax.profiler.TraceAnnotation(spans.CHECKPOINT):
                checkpoint.save(
                    os.path.join(checkpoint_dir, f"step_{r + 1}"),
                    carry.state, r + 1,
                )
    return carry.state, last


# --------------------------------------------------------------------------
# pipelined rounds under bounded staleness (DESIGN.md §14)
# --------------------------------------------------------------------------

# SeedSequence tag for the busy-aware uniform cohort draw of the pipelined
# driver; disjoint from cohort.py (53/59/211) and faults.py (101..113)
_TAG_FREE = 223


def make_pipelined_round_fn(
    cfg: ModelConfig,
    tcfg: tamuna_dp.DistTamunaConfig,
    mesh,
    *,
    sample_batch: SampleFn,
    max_L: int = 16,
    n: Optional[int] = None,
    elastic: Optional[bool] = None,
    coverage: bool = True,
):
    """Build the split-phase round engine ``run_rounds_pipelined`` drives.

    Where ``make_round_fn`` fuses gather -> local steps -> scatter -> comm
    into one donated program per chunk, this engine compiles the round as
    two separately dispatchable halves so the driver can interleave rounds:

      ``stage(carry, data, L, cohort) -> (carry, buf)``
          gather the cohort rows into a compact ``(c, ...)`` payload
          buffer and run the round's ``L`` local steps there (same
          ``round_chunks`` bucketing and compile-cache bound as the fused
          engine).  The carry's full state and traces are passed through
          untouched — a staged round owns nothing but its compact buffer,
          its summed loss, and its step count, all returned in ``buf``.
          The pending buffers of in-flight rounds ARE the double-buffer:
          at ``τ=1`` two compact states ping-pong while the full state
          advances underneath them.

      ``commit(carry, buf, slot, cohort, down, ...) -> carry``
          scatter the staged rows back, run the comm step (UpCom,
          h-update, DownCom to ``down``), inject/guard faults when an
          ``arrived`` mask is given (identical semantics to the fused
          engine's fault branch, DESIGN.md §12), and write ALL of the
          round's traces at ``slot``.  Commits happen in round order, so
          ``state.round`` inside the program is exactly the committing
          round's global index — the comm key replays bit-identically to
          the synchronous engine.

    Soundness rests on the driver's no-overlap invariant: in-flight
    cohorts are pairwise disjoint, so between a round's gather and its
    commit nothing touches its cohort's rows — the deferred scatter+comm
    reads exactly the payload a synchronous round would have read.

    ``coverage=True`` additionally compiles the stats-reporting comm step
    (``tamuna_dp.make_comm_step(with_stats=True)``): fault-tolerant
    commits then trace the number of coordinates the survivor-aware UpCom
    left uncovered — the quality signal the staleness sweeps plot.

    Returns an engine namespace with ``stage``/``commit`` plus the same
    introspection attributes as the fused engine (``cache``, ``max_L``,
    ``n``, ``c``, ``elastic``, and ``coverage``).
    """
    import types

    n = n or sharding.n_clients(mesh)
    c = tcfg.c
    if elastic is None:
        elastic = default_elastic(n, c, sharding.n_clients(mesh))
    local = tamuna_dp.make_local_step(cfg, tcfg)
    comm = tamuna_dp.make_comm_step(cfg, tcfg, mesh, n=n)
    comm_stats = (tamuna_dp.make_comm_step(cfg, tcfg, mesh, n=n,
                                           with_stats=True)
                  if coverage else None)

    def stage_chunk(B: int, carry: RoundCarry, compact, loss, data, clients):
        state, t, dk, ck, traces = carry
        compact, t, ls = _scan_local(
            local, sample_batch, compact, data, _as_key(dk), t, B,
            clients=clients,
        )
        return RoundCarry(state, t, dk, ck, traces), compact, loss + ls

    def stage_chunk_full(B: int, carry: RoundCarry, loss, data):
        state, t, dk, ck, traces = carry
        state, t, ls = _scan_local(
            local, sample_batch, state, data, _as_key(dk), t, B
        )
        return RoundCarry(state, t, dk, ck, traces), loss + ls

    def commit_fn(carry: RoundCarry, compact, loss, steps, slot, cohort,
                  down, arrived=None, corrupt=None, byz=None, *,
                  correct: bool = True, guard: bool = False,
                  guard_mode: str = "nonfinite",
                  corrupt_mode: str = "nan", blowup: float = 1e8,
                  guard_max_abs: Optional[float] = None,
                  adversary: str = "none", byz_scale: float = -10.0,
                  byz_z: float = 1.5) -> RoundCarry:
        state, t, dk, ck, traces = carry
        if elastic:
            state = tamuna_dp.scatter_cohort(state, compact, cohort)
        else:
            # all-rows body: every row trained during stage, so the
            # DownCom must broadcast (see make_round_fn)
            down = None
        with jax.named_scope(spans.COMM_STEP):
            ckey = jax.random.key_data(comm_round_key(ck, state.round))
            if arrived is None:
                state = comm(state, ckey, cohort=cohort, down=down)
                arr = None
            else:
                from repro.dist import faults as faults_mod
                from repro.dist import robust as robust_mod

                member = jnp.zeros((n,), bool).at[cohort].set(True)
                stx = state
                if corrupt is not None:
                    stx = stx._replace(x=faults_mod.corrupt_rows(
                        stx.x, corrupt, corrupt_mode, blowup
                    ))
                arr = arrived & member
                if byz is not None:
                    stx = stx._replace(x=faults_mod.adversarial_rows(
                        stx.x, byz & arr, arr & ~byz, adversary,
                        byz_scale=byz_scale, byz_z=byz_z,
                    ))
                if guard:
                    bad = faults_mod.nonfinite_clients(
                        stx.x, guard_max_abs
                    ) & member
                    if guard_mode == "adaptive":
                        bad = bad | (robust_mod.magnitude_outliers(
                            stx.x, arr & ~bad
                        ) & member)
                    arr = arr & ~bad
                    stx = stx._replace(x=jax.tree.map(
                        lambda a: jnp.where(
                            bad.reshape((n,) + (1,) * (a.ndim - 1)),
                            jnp.zeros((), a.dtype), a,
                        ),
                        stx.x,
                    ))
                else:
                    bad = jnp.zeros((n,), bool)
                if comm_stats is not None and "uncovered" in traces:
                    state, stats = comm_stats(stx, ckey, cohort=cohort,
                                              down=down, arrived=arr,
                                              correct=correct)
                    unc = stats["uncovered"]
                else:
                    state = comm(stx, ckey, cohort=cohort, down=down,
                                 arrived=arr, correct=correct)
                    unc = None
        with jax.named_scope(spans.TRACES):
            out_traces = {
                "loss_sum": traces["loss_sum"].at[slot].set(loss),
                "steps": traces["steps"].at[slot].set(steps),
                "up_floats": traces["up_floats"].at[slot].set(
                    state.up_floats
                ),
                "down_floats": traces["down_floats"].at[slot].set(
                    state.down_floats
                ),
                "up_bytes": traces["up_bytes"].at[slot].set(state.up_bytes),
                "down_bytes": traces["down_bytes"].at[slot].set(
                    state.down_bytes
                ),
            }
            if arr is not None:
                out_traces.update({
                    "arrivals": traces["arrivals"].at[slot].set(
                        arr.sum().astype(jnp.int32)
                    ),
                    "corrupted": traces["corrupted"].at[slot].set(
                        bad.sum().astype(jnp.int32)
                    ),
                    "bad": traces["bad"].at[slot].set(bad),
                })
                if unc is not None:
                    out_traces["uncovered"] = traces["uncovered"].at[
                        slot].set(unc)
        return RoundCarry(state, t, dk, ck, out_traces)

    cache: Dict[Any, Callable] = {}

    def gather_prog():
        if "gather" not in cache:
            # NOT donated: the full state stays live in the carry
            cache["gather"] = jax.jit(tamuna_dp.gather_cohort)
        return cache["gather"]

    def stage_prog(B: int):
        key = ("stage", B)
        if key not in cache:
            fn = stage_chunk if elastic else stage_chunk_full
            dn = (0, 1, 2) if elastic else (0, 1)
            cache[key] = jax.jit(partial(fn, B), donate_argnums=dn)
        return cache[key]

    def commit_prog(fkey):
        # only the carry is donated: the (c, ...) compact payload cannot
        # alias any (n, ...) output, so donating it would just warn
        key = ("commit", fkey)
        if key not in cache:
            if fkey is None:
                cache[key] = jax.jit(commit_fn, donate_argnums=(0,))
            else:
                (correct, guard, gmode, mode, blowup, gmax,
                 adversary, bscale, bz) = fkey
                cache[key] = jax.jit(
                    partial(commit_fn, correct=correct, guard=guard,
                            guard_mode=gmode, corrupt_mode=mode,
                            blowup=blowup, guard_max_abs=gmax,
                            adversary=adversary, byz_scale=bscale,
                            byz_z=bz),
                    donate_argnums=(0,),
                )
        return cache[key]

    def stage(carry: RoundCarry, data, L: int, cohort=None):
        chunks = round_chunks(L, max_L)
        loss = jnp.float32(0.0)
        if elastic:
            if cohort is None:
                raise ValueError("elastic stage needs a host-resolved "
                                 "cohort (the driver owns the schedule)")
            cohort = jnp.asarray(cohort, jnp.int32)
            compact = gather_prog()(carry.state, cohort)
            for B in chunks:
                carry, compact, loss = stage_prog(B)(
                    carry, compact, loss, data, cohort
                )
            return carry, {"compact": compact, "loss": loss,
                           "steps": sum(chunks)}
        for B in chunks:
            carry, loss = stage_prog(B)(carry, loss, data)
        return carry, {"compact": None, "loss": loss, "steps": sum(chunks)}

    def commit(carry: RoundCarry, buf, slot, cohort=None, down=None,
               arrived=None, corrupt=None, byz=None,
               correct: bool = True,
               guard: bool = False, guard_mode: str = "nonfinite",
               corrupt_mode: str = "nan", blowup: float = 1e8,
               guard_max_abs: Optional[float] = None,
               adversary: str = "none", byz_scale: float = -10.0,
               byz_z: float = 1.5) -> RoundCarry:
        slot = jnp.asarray(slot, jnp.int32)
        steps = jnp.asarray(buf["steps"], jnp.int32)
        if elastic and cohort is None:
            raise ValueError("elastic commit needs the staged cohort")
        if cohort is not None:
            cohort = jnp.asarray(cohort, jnp.int32)
        if down is not None:
            down = jnp.asarray(down).astype(bool)
        if arrived is None:
            if corrupt is not None:
                raise ValueError("corrupt mask needs an arrived mask")
            return commit_prog(None)(
                carry, buf["compact"], buf["loss"], steps, slot, cohort,
                down,
            )
        if cohort is None:
            raise ValueError("fault-tolerant commit needs an explicit "
                             "cohort (resolve it host-side)")
        fkey = (bool(correct), bool(guard), str(guard_mode),
                str(corrupt_mode), float(blowup),
                None if guard_max_abs is None else float(guard_max_abs),
                str(adversary), float(byz_scale), float(byz_z))
        arrived = jnp.asarray(arrived).astype(bool)
        if corrupt is not None:
            corrupt = jnp.asarray(corrupt).astype(bool)
        if byz is not None:
            byz = jnp.asarray(byz).astype(bool)
        return commit_prog(fkey)(
            carry, buf["compact"], buf["loss"], steps, slot, cohort, down,
            arrived, corrupt, byz,
        )

    return types.SimpleNamespace(
        stage=stage, commit=commit, cache=cache, max_L=max_L, n=n, c=c,
        elastic=elastic, coverage=comm_stats is not None,
    )


def _uniform_cohort_host(ck0, g: int, n: int, c: int,
                         attempt: int = 0):
    """Host replay of the engine's on-device uniform cohort for round
    ``g`` — bit-identical to the in-program derivation (same key fold,
    same ``round_cohort``), so explicit upload preserves the fault-free
    schedule exactly."""
    import numpy as np

    ckey = comm_round_key(jnp.asarray(ck0), g)
    if attempt > 0:
        ckey = jax.random.fold_in(ckey, attempt)
    return np.asarray(jax.device_get(tamuna_dp.round_cohort(ckey, n, c)))


def _free_uniform_cohort(ck0, g: int, n: int, c: int, busy):
    """Uniform cohort over the FREE clients only: with rounds in flight a
    busy client physically cannot join a new cohort, so the pipelined
    driver draws round ``g``'s cohort uniformly from the complement of
    the in-flight set.  Deterministic in ``(comm_key, g, busy)`` — keyed
    off the same per-round comm key as the synchronous schedule, under a
    dedicated stream tag so it never correlates with other draws."""
    import numpy as np

    busy = np.asarray(busy, bool)
    free = np.where(~busy)[0]
    if free.size < c:
        raise ValueError(
            f"only {free.size} free clients for c={c} at round {g}: "
            f"staleness too deep for this fleet (need c*(tau+1) <= n)"
        )
    kd = np.asarray(jax.device_get(jax.random.key_data(
        comm_round_key(jnp.asarray(ck0), g)
    ))).reshape(-1)
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(kd[0]), int(kd[1]), _TAG_FREE]
    ))
    pick = rng.choice(free.size, size=c, replace=False)
    return np.sort(free[pick]).astype(np.int32)


def run_rounds_pipelined(
    state: tamuna_dp.DistTamunaState,
    *,
    round_fn,
    data: Any,
    key: jax.Array,
    rounds: int,
    rng,
    p: float,
    staleness: int = 1,
    flush_every: int = 10,
    logger=None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    max_L: Optional[int] = None,
    plan=None,
    faults=None,
    latency=None,
    policy: str = "wait_all",
    quorum: Optional[int] = None,
    max_retries: int = 3,
    backoff0: float = 1.0,
    deadline: Optional[float] = None,
    guard: Optional[bool] = None,
    guard_max_abs: Optional[float] = None,
    guard_mode: Optional[str] = None,
    resume: bool = False,
) -> Tuple[tamuna_dp.DistTamunaState, Dict[str, Any]]:
    """Pipelined multi-round driver: overlap local compute with
    communication under bounded staleness ``τ = staleness``.

    Pipeline step ``u`` first *stages* round ``u`` (cohort gather + local
    steps into a pending payload buffer) and then *commits* round
    ``u - τ`` (scatter + UpCom/h-update/DownCom + traces), so up to ``τ``
    rounds are in flight at once and a committing round's stragglers had
    ``τ`` extra rounds of wall-clock to land.  ``τ=0`` stages and commits
    the same round back to back — the identical op sequence (and, under a
    ``FaultPlan``, the identical host-side survivor resolution) as
    ``run_rounds``.

    Schedule invariants, all host-enforced:

      * **Disjoint in-flight cohorts** — round ``g``'s cohort is drawn
        from the clients NOT in the ``τ`` preceding uncommitted rounds
        (``plan.cohort_excluding`` / ``_free_uniform_cohort``; requires
        ``c·(τ+1) <= n`` and the elastic engine).  This is what makes the
        deferred commit exact: nothing touches a staged cohort's rows
        between gather and commit.
      * **DownCom prefetch** — commit of round ``g`` targets the cohort
        of round ``g+τ+1``, the round that stages immediately after this
        commit: joining clients receive ``x_bar`` exactly one commit
        before their gather, never earlier, never later.  (At ``τ=0``
        this is round ``g+1`` — the synchronous rule.)
      * **Bounded-staleness admission** — at ``τ>=1`` the simulated
        clock decides lateness: a member's uplink arrives at
        ``dispatch_g + delay_i(g)·L_g`` (per-step latency draws from
        ``latency`` — a ``faults.EmpiricalDelays`` or any object with
        ``.delays(rnd, attempt)`` — or from the ``FaultPlan``); the
        policy's cutoff (``wait_all`` = slowest member, ``quorum`` =
        q-th arrival, ``deadline`` = dispatch + deadline) admits rows
        into the deferred rebuild through PR 6's ``arrived``-mask
        survivor aggregation and demotes the rest to dropped — their
        coordinates stay bitwise untouched.  Unlike the synchronous
        quorum, a quorum miss never resamples (the pipeline cannot
        rewind a staged round); it commits whatever arrived.  At ``τ=0``
        with a ``FaultPlan`` the synchronous resolver (retries, backoff,
        resampling) is reused verbatim.

    The simulated wall clock (the benchmark's headline) advances as
    ``dispatch_u = max(commit_{u-τ-1}, dispatch_{u-1})`` and
    ``commit_g = max(commit_{g-1}, cutoff_g)`` — at ``τ=0``/``wait_all``
    this reproduces the bulk-synchronous sum-of-slowest-member cost
    model of ``examples/availability_sim.py``; at ``τ>=1`` a straggler
    only stalls the clock if it is still missing ``τ`` rounds later.
    Metrics rows gain ``staleness``/``dispatch_s``/``commit_s``/
    ``round_latency_s``/``admitted``/``late_dropped`` (plus
    ``uncovered`` when the engine traces coverage); the final row's
    ``commit_s`` is the run's total simulated seconds.

    ``checkpoint_every`` saves a *pipeline* checkpoint (the carry plus
    every in-flight payload buffer and the clock —
    ``pipeline_checkpoint_save``) at trace-drain boundaries while the
    pipeline is full; ``resume=True`` restores the latest one and
    continues bit-exactly (the host ``rng``'s skipped ``L`` draws are
    replayed deterministically).

    Caveat (documented, by design): AdamW's shared ``opt.count`` scalar
    is scattered back last-wins, so under pipelining its value can lag
    the true global step by up to ``τ·max_L`` — same order as the
    staleness the optimizer already tolerates.
    """
    import numpy as np

    engine = round_fn
    if not (hasattr(engine, "stage") and hasattr(engine, "commit")):
        raise ValueError("run_rounds_pipelined needs the split-phase "
                         "engine from make_pipelined_round_fn")
    tau = int(staleness)
    if tau < 0:
        raise ValueError(f"staleness must be >= 0, got {tau}")
    n, c = engine.n, engine.c
    engine_cap = engine.max_L
    max_L = min(max_L or engine_cap, engine_cap)
    flush_every = max(1, min(flush_every, rounds))
    if policy not in ROUND_POLICIES:
        raise ValueError(f"unknown policy {policy!r}; pick from "
                         f"{ROUND_POLICIES}")
    if policy == "deadline" and deadline is None:
        raise ValueError("deadline policy needs a deadline (seconds)")
    if tau >= 1:
        if not engine.elastic:
            raise ValueError(
                "pipelining (staleness >= 1) needs the elastic engine: "
                "all-rows rounds touch every client row, so in-flight "
                "rounds cannot be disjoint"
            )
        if c * (tau + 1) > n:
            raise ValueError(
                f"staleness {tau} needs c*(tau+1) <= n "
                f"(got c={c}, n={n}): in-flight cohorts must be disjoint"
            )
    adversarial = faults is not None and faults.model.adversarial
    if guard is None:
        guard = faults is not None and (faults.model.p_corrupt > 0
                                        or adversarial)
    if guard_mode is None:
        # same ISSUE 9 default as run_rounds: finite corruption needs
        # the adaptive magnitude guard, not just the nonfinite check
        guard_mode = ("adaptive" if bool(guard) and guard_max_abs is None
                      and faults is not None
                      and (adversarial
                           or (faults.model.p_corrupt > 0
                               and faults.model.corrupt_mode == "blowup"))
                      else "nonfinite")
    if guard_mode not in ("nonfinite", "adaptive"):
        raise ValueError(f"unknown guard_mode {guard_mode!r}; pick "
                         "'nonfinite' or 'adaptive'")
    byz_mask = jnp.asarray(faults.byzantine) if adversarial else None
    if faults is not None and faults.n != n:
        raise ValueError(f"fault plan covers {faults.n} clients, "
                         f"engine has n={n}")
    if policy != "wait_all" and faults is None and (tau == 0
                                                    or latency is None):
        raise ValueError("round policies need a fault plan "
                         "(or, at staleness >= 1, a latency model)")
    lat_n = getattr(latency, "n", None)
    if lat_n is not None and lat_n != n:
        raise ValueError(f"latency model covers {lat_n} clients, "
                         f"engine has n={n}")

    robust = (faults is not None and (
        not faults.is_zero or policy != "wait_all" or bool(guard)
    )) or (tau >= 1 and policy != "wait_all")
    sync_equiv = tau == 0 and robust  # reuse the synchronous resolver
    q = quorum if quorum is not None else c // 2 + 1
    coverage = bool(getattr(engine, "coverage", False)) and robust
    r0 = int(state.round)
    with jax.profiler.TraceAnnotation(spans.INIT_CARRY):
        carry = init_carry(state, key, flush_every,
                           robust_n=n if robust else 0, coverage=coverage)
    ck0 = np.asarray(jax.device_get(carry.comm_key))

    def host_cohort(g: int, attempt: int = 0) -> np.ndarray:
        if plan is not None:
            return np.asarray(plan.cohort(g, attempt))
        return _uniform_cohort_host(ck0, g, n, c, attempt)

    resolve = (_make_fault_resolver(
        faults, n=n, policy=policy, q=q, max_retries=max_retries,
        backoff0=backoff0, deadline=deadline, host_cohort=host_cohort,
    ) if sync_equiv else None)

    cohorts: Dict[int, np.ndarray] = {}

    def resolve_cohort(g: int, busy: np.ndarray) -> np.ndarray:
        got = cohorts.get(g)
        if got is not None:
            return got
        if plan is not None:
            co = np.asarray(plan.cohort_excluding(g, busy) if tau >= 1
                            else plan.cohort(g))
        elif not busy.any():
            co = _uniform_cohort_host(ck0, g, n, c)
        else:
            co = _free_uniform_cohort(ck0, g, n, c, busy)
        cohorts[g] = co
        return co

    def busy_mask() -> np.ndarray:
        busy = np.zeros(n, bool)
        for e in pend:
            if e["cohort"] is not None:
                busy[e["cohort"]] = True
        return busy

    lat_src = latency if latency is not None else faults

    def arr_offsets(g: int, steps: int, attempt: int = 0) -> np.ndarray:
        """(n,) absolute arrival offsets: per-STEP latency draws times
        the round's local-step count (the availability_sim cost model)."""
        if lat_src is None:
            return np.zeros(n)
        return (np.asarray(lat_src.delays(g, attempt), np.float64)
                * max(int(steps), 1))

    pend: list = []  # in-flight staged rounds, oldest first
    window: list = []  # per-committed-round host meta awaiting drain
    dispatch: Dict[int, float] = {}
    committime: Dict[int, float] = {}
    total_steps = 0
    last: Dict[str, Any] = {}
    u0 = 0

    if resume:
        if not checkpoint_dir:
            raise ValueError("resume=True needs a checkpoint_dir")
        step = pipeline_latest_step(checkpoint_dir)
        if step is not None:
            blob = pipeline_checkpoint_restore(
                os.path.join(checkpoint_dir, f"pipe_step_{step}"),
                carry_like=carry, engine=engine,
            )
            carry = blob["carry"]._replace(
                traces=_zero_traces(flush_every, n if robust else 0,
                                    coverage)
            )
            for e in blob["pending"]:
                r = int(e["r"])
                co = (None if e["cohort"] is None
                      else np.asarray(e["cohort"], np.int32))
                if co is not None:
                    cohorts[r0 + r] = co
                d = float(e["dispatch"])
                dispatch[r] = d
                pend.append({
                    "r": r, "cohort": co, "dispatch": d,
                    "buf": {"compact": e["compact"], "loss": e["loss"],
                            "steps": int(e["steps"])},
                })
            u0 = step + len(pend)
            committime[step - 1] = float(blob["clock"]["last_commit"])
            if not pend:
                dispatch[u0 - 1] = float(blob["clock"]["last_dispatch"])
            total_steps = int(blob["clock"]["total_steps"])
            # replay (and discard) the L draws of already-staged rounds so
            # the host rng continues the original stream bit-exactly
            for _ in range(u0):
                tamuna_dp.sample_round_length(rng, p, max_L=max_L)

    for u in range(u0, rounds + tau):
        # one pipeline step: stage round u, commit round u - tau
        with jax.profiler.TraceAnnotation(spans.ROUND):
            if u < rounds:
                # ---- stage round u
                L = tamuna_dp.sample_round_length(rng, p, max_L=max_L)
                g = r0 + u
                if sync_equiv:
                    co = np.asarray(resolve(g)["cohort"])
                elif engine.elastic:
                    co = resolve_cohort(g, busy_mask())
                elif plan is not None:
                    co = np.asarray(plan.cohort(g))
                else:
                    co = None
                carry, buf = engine.stage(carry, data, L, cohort=co)
                d = max(committime.get(u - tau - 1, 0.0),
                        dispatch.get(u - 1, 0.0))
                dispatch[u] = d
                pend.append({"r": u, "cohort": co, "buf": buf, "dispatch": d})
            rc = u - tau
            if not (0 <= rc < rounds):
                continue
            # ---- commit round rc
            ent = pend.pop(0)
            g = r0 + rc
            co, buf = ent["cohort"], ent["buf"]
            if engine.elastic:
                if sync_equiv:
                    down = resolve(g + 1)["member"]
                else:
                    nxt = resolve_cohort(g + tau + 1, busy_mask())
                    down = np.zeros(n, bool)
                    down[nxt] = True
            else:
                down = None
            kw: Dict[str, Any] = {}
            meta: Dict[str, Any] = {"staleness": tau}
            if sync_equiv:
                res = resolve(g)
                arr_off = arr_offsets(g, buf["steps"], res["retries"])
                arr = ent["dispatch"] + arr_off
                cutoff = (float(arr[res["arrived"]].max())
                          if res["arrived"].any() else ent["dispatch"])
                cutoff += res["backoff"]
                kw = dict(
                    arrived=res["arrived"],
                    corrupt=(res["corrupt"]
                             if faults.model.p_corrupt > 0 else None),
                    byz=byz_mask,
                    correct=(policy != "wait_all"), guard=bool(guard),
                    guard_mode=guard_mode,
                    corrupt_mode=faults.model.corrupt_mode,
                    blowup=faults.model.blowup, guard_max_abs=guard_max_abs,
                    adversary=faults.model.adversary,
                    byz_scale=faults.model.byz_scale,
                    byz_z=faults.model.byz_z,
                )
                meta.update(
                    retries=res["retries"], backoff_s=res["backoff"],
                    quorum_miss=res["quorum_miss"],
                    admitted=int(res["arrived"].sum()), late_dropped=0,
                )
            elif robust:
                member = np.zeros(n, bool)
                member[co] = True
                dropped = (faults.drops(g, 0) if faults is not None
                           else np.zeros(n, bool))
                finite = member & ~dropped
                arr = np.where(finite,
                               ent["dispatch"] + arr_offsets(g, buf["steps"]),
                               np.inf)
                if policy == "wait_all":
                    cutoff = (float(arr[finite].max()) if finite.any()
                              else ent["dispatch"])
                    admitted = finite
                elif policy == "quorum":
                    kq = min(q, int(finite.sum()))
                    if kq == 0:
                        cutoff, admitted = ent["dispatch"], np.zeros(n, bool)
                    else:
                        cutoff = float(np.sort(arr[finite])[kq - 1])
                        admitted = finite & (arr <= cutoff)
                else:
                    # deadline cuts on simulated ARRIVAL time here (the
                    # synchronous run_rounds cuts on the raw per-round draw)
                    cutoff = ent["dispatch"] + float(deadline)
                    admitted = finite & (arr <= cutoff)
                kw = dict(
                    arrived=admitted,
                    corrupt=(faults.corrupts(g, 0) & member
                             if faults is not None
                             and faults.model.p_corrupt > 0 else None),
                    byz=byz_mask,
                    correct=(policy != "wait_all"), guard=bool(guard),
                    guard_mode=guard_mode,
                    corrupt_mode=(faults.model.corrupt_mode
                                  if faults is not None else "nan"),
                    blowup=(faults.model.blowup
                            if faults is not None else 1e8),
                    guard_max_abs=guard_max_abs,
                    adversary=(faults.model.adversary
                               if faults is not None else "none"),
                    byz_scale=(faults.model.byz_scale
                               if faults is not None else -10.0),
                    byz_z=(faults.model.byz_z
                           if faults is not None else 1.5),
                )
                meta.update(
                    retries=0, backoff_s=0.0,
                    quorum_miss=int(policy == "quorum"
                                    and int(finite.sum()) < q),
                    admitted=int(admitted.sum()),
                    late_dropped=int((finite & ~admitted).sum()),
                )
            else:
                # no admission needed (everyone arrives): the clock still
                # waits for the slowest member — the wait_all barrier
                off = arr_offsets(g, buf["steps"])
                if co is not None:
                    member = np.zeros(n, bool)
                    member[co] = True
                    cutoff = ent["dispatch"] + (
                        float(off[member].max()) if member.any() else 0.0
                    )
                    meta.update(admitted=int(member.sum()), late_dropped=0)
                else:
                    cutoff = ent["dispatch"] + (
                        float(off.max()) if off.size else 0.0
                    )
                    meta.update(admitted=n, late_dropped=0)
            tc = max(committime.get(rc - 1, 0.0), cutoff)
            committime[rc] = tc
            carry = engine.commit(carry, buf, len(window), cohort=co,
                                  down=down, **kw)
            meta.update({
                "round": rc, "dispatch_s": ent["dispatch"], "commit_s": tc,
                "round_latency_s": tc - ent["dispatch"],
            })
            window.append(meta)
        drained = False
        if len(window) == flush_every or rc == rounds - 1:
            with jax.profiler.TraceAnnotation(spans.DRAIN):
                tr = jax.device_get(carry.traces)  # the only host sync
            with jax.profiler.TraceAnnotation(spans.FLUSH):
                for i, m in enumerate(window):
                    executed = int(tr["steps"][i])
                    total_steps += executed
                    last = {
                        "round": m["round"],
                        "L": executed,
                        "loss": (float(tr["loss_sum"][i])
                                 / max(executed, 1)),
                        "local_steps": total_steps,
                        "up_floats": float(tr["up_floats"][i]),
                        "down_floats": float(tr["down_floats"][i]),
                        "up_bytes": float(tr["up_bytes"][i]),
                        "down_bytes": float(tr["down_bytes"][i]),
                    }
                    if robust:
                        last["arrivals"] = int(tr["arrivals"][i])
                        last["corrupted"] = int(tr["corrupted"][i])
                        if "uncovered" in tr:
                            last["uncovered"] = int(tr["uncovered"][i])
                    last.update({k: v for k, v in m.items()
                                 if k != "round"})
                    if logger is not None:
                        logger.log(m["round"], last)
                window = []
                carry = carry._replace(traces=_zero_traces(
                    flush_every, n if robust else 0, coverage
                ))
            drained = True
        if (drained and checkpoint_dir and checkpoint_every
                and (rc + 1) % checkpoint_every == 0
                and len(pend) == tau and rc + 1 < rounds):
            with jax.profiler.TraceAnnotation(spans.CHECKPOINT):
                pipeline_checkpoint_save(
                    os.path.join(checkpoint_dir, f"pipe_step_{rc + 1}"),
                    carry, pend,
                    {"last_dispatch": np.float64(dispatch.get(u, 0.0)),
                     "last_commit": np.float64(tc),
                     "total_steps": np.int32(total_steps)},
                    rc + 1,
                )
    return carry.state, last


def pipeline_checkpoint_save(path: str, carry: RoundCarry, pending,
                             clock, step: int) -> None:
    """Atomically checkpoint a pipelined run mid-flight: the donated
    carry, every in-flight payload buffer (compact state + loss + step
    count + cohort + dispatch time), and the simulated clock — one
    ``checkpoint.save`` tree, so a restored run continues bit-exactly
    with both buffers in flight.  Saved under ``pipe_step_<k>`` (``k``
    committed rounds), a namespace disjoint from the synchronous
    ``step_<k>`` state checkpoints."""
    import numpy as np

    from repro import checkpoint

    pend = tuple(
        {
            "compact": e["buf"]["compact"],
            "loss": e["buf"]["loss"],
            "steps": np.int32(e["buf"]["steps"]),
            "r": np.int32(e["r"]),
            "cohort": (None if e["cohort"] is None
                       else np.asarray(e["cohort"], np.int32)),
            "dispatch": np.float64(e["dispatch"]),
        }
        for e in pending
    )
    checkpoint.save(path, {"carry": carry, "pending": pend,
                           "clock": dict(clock)}, step)


def pipeline_checkpoint_restore(path: str, *, carry_like: RoundCarry,
                                engine):
    """Restore a ``pipeline_checkpoint_save`` blob.  The number of
    in-flight buffers is read from the checkpoint's own leaf names (the
    pipeline depth is a runtime choice, not a structural constant); the
    per-buffer ``like`` comes from ``jax.eval_shape`` of the engine's
    gather, so no device work happens until the arrays land."""
    import json
    import numpy as np

    from repro import checkpoint

    with open(os.path.join(path, "meta.json")) as f:
        names = json.load(f)["names"]
    idx = {int(nm.split("/")[1]) for nm in names
           if nm.startswith("pending/")}
    k = (max(idx) + 1) if idx else 0
    if engine.elastic:
        compact_like = jax.eval_shape(
            tamuna_dp.gather_cohort, carry_like.state,
            jax.ShapeDtypeStruct((engine.c,), jnp.int32),
        )
        cohort_like = np.zeros((engine.c,), np.int32)
    else:
        compact_like, cohort_like = None, None
    entry_like = {
        "compact": compact_like,
        "loss": jax.ShapeDtypeStruct((), jnp.float32),
        "steps": np.int32(0),
        "r": np.int32(0),
        "cohort": cohort_like,
        "dispatch": np.float64(0.0),
    }
    like = {
        "carry": carry_like,
        "pending": tuple(entry_like for _ in range(k)),
        "clock": {"last_dispatch": np.float64(0.0),
                  "last_commit": np.float64(0.0),
                  "total_steps": np.int32(0)},
    }
    return checkpoint.restore(path, like)


def pipeline_latest_step(root: str) -> Optional[int]:
    """Newest ``pipe_step_<k>`` checkpoint under ``root`` (committed
    rounds ``k``), or None."""
    if not os.path.isdir(root):
        return None
    steps = [
        int(d.split("_")[-1]) for d in os.listdir(root)
        if d.startswith("pipe_step_")
    ]
    return max(steps) if steps else None
