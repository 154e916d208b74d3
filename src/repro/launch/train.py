"""End-to-end TAMUNA-DP training driver.

Runs real training on the devices JAX finds: the TPU chips of a TPU host,
or forced host CPU devices elsewhere (``--data-parallel`` x
``--model-parallel`` of them; the same step functions the dry-run lowers
for the production mesh).  The first line printed names the platform, so a
run that expected a chip and got the CPU says so.  Round structure follows
Algorithm 1: ``L^(r) ~ Geometric(p)`` local steps then a compressed
communication step.

By default the round is ONE compiled unit: the fused round engine
(``repro.dist.rounds``) scans the local steps with donated state, samples
batches on device from scan-carried PRNG keys, runs the comm step in the
same program, and accumulates metrics on device (drained every
``--flush-every`` rounds).  ``--no-fuse`` keeps the legacy per-step path
(one jit dispatch per local step, host-sampled batches) as an escape hatch
— still with donated state buffers.

Example (the (b) deliverable end-to-end driver):
  PYTHONPATH=src python -m repro.launch.train \
      --arch gemma2-2b --reduced --rounds 30 --seq-len 128 \
      --per-client-batch 2 --data-parallel 4 --model-parallel 2
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time


def main(argv=None, *, round_fn_hook=None) -> int:
    """Parse ``argv`` and train.  ``round_fn_hook``, if given, is called
    with the synchronous driver's round function after the last round
    (``round_fn.lowered()`` then yields the programs that ran)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--per-client-batch", type=int, default=2)
    ap.add_argument("--data-parallel", type=int, default=4)
    ap.add_argument("--model-parallel", type=int, default=2)
    ap.add_argument("--gamma", type=float, default=0.05)
    ap.add_argument("--p", type=float, default=0.34)
    ap.add_argument("--cohort", type=int, default=0, help="0 = 3n/4")
    ap.add_argument("--clients", type=int, default=0,
                    help="population n (0 = one client per data shard); "
                         "n > dp stacks n/dp client rows per shard")
    ap.add_argument("--sparsity", type=int, default=2)
    ap.add_argument("--uplink", default="masked_psum",
                    choices=["masked_psum", "block_rs"])
    # literal list (= comm_ws.COMM_IMPLS): this module must not import
    # repro/jax before main() sets XLA_FLAGS; DistTamunaConfig re-validates
    ap.add_argument("--comm-impl", default="auto",
                    choices=["auto", "dense", "ws", "pallas"],
                    help="comm-step aggregation path (DESIGN.md §9/§10): "
                         "psum-shaped fused partials (ws), the "
                         "shard-resident shard_map'd kernel engine "
                         "(pallas; per-shard uplinks + one d-sized psum), "
                         "or the per-leaf dense-mask reference (dense)")
    # literal list (= wire.WIRE_POLICIES): same no-early-jax rule as above
    ap.add_argument("--wire-precision", default="f32",
                    choices=["auto", "f32", "bf16", "f16", "int8", "int4"],
                    help="UpCom payload width (DESIGN.md §13): f32 is the "
                         "unquantized wire, auto resolves per leaf size "
                         "(small leaves f16, large 8-bit stochastic)")
    ap.add_argument("--wire-down", action="store_true",
                    help="also quantize the DownCom broadcast (needs a "
                         "non-f32 --wire-precision)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log", default="")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--no-fuse", action="store_true",
                    help="legacy per-step driver: one jit dispatch per "
                         "local step, host-sampled batches")
    ap.add_argument("--max-L", type=int, default=16,
                    help="cap on the geometric round length")
    ap.add_argument("--flush-every", type=int, default=10,
                    help="fused path: drain device metric traces every "
                         "this many rounds")
    ap.add_argument("--profile-dir", default="",
                    help="fused synchronous rounds: write jax.profiler "
                         "traces to DIR/setup and DIR/rounds.  The first "
                         "k*k+1 rounds, for k bucket lengths (1, 2, 4, ... "
                         "up to --max-L), take fixed lengths in place of "
                         "drawn ones, so the run trains another trajectory "
                         "than without the flag.  DIR/setup holds those "
                         "rounds, each program's trace, lowering and "
                         "compile under its tamuna.compile.<bucket> span; "
                         "DIR/rounds holds the rest, with nothing compiled")
    ap.add_argument("--pipeline", action="store_true",
                    help="pipelined round engine (DESIGN.md §14): overlap "
                         "round t+1's local compute with round t's "
                         "commit under bounded staleness")
    ap.add_argument("--staleness", type=int, default=1,
                    help="pipeline depth tau: a round's commit may lag "
                         "its dispatch by this many rounds (0 = the "
                         "synchronous schedule, run through the split-"
                         "phase engine)")
    ap.add_argument("--latency-dist", default="",
                    help="path to an availability_sim --dist export; "
                         "drives the pipelined driver's simulated clock "
                         "(per-step straggler latencies)")
    ap.add_argument("--round-policy", default="wait_all",
                    choices=["wait_all", "quorum", "deadline"],
                    help="pipelined admission policy at the deferred "
                         "commit (late uplinks past the cutoff are "
                         "dropped, their coordinates untouched)")
    ap.add_argument("--quorum", type=int, default=0,
                    help="quorum size for --round-policy quorum "
                         "(0 = c//2 + 1)")
    # literal list (= robust.ROBUST_AGGS): same no-early-jax rule as above
    ap.add_argument("--robust-agg", default="mean",
                    choices=["mean", "trimmed", "median"],
                    help="per-coordinate combiner over the s arrived "
                         "owner values (DESIGN.md §15): trimmed drops "
                         "--trim-k per side, median takes the middle; "
                         "mean (or trimmed with k=0) is the bitwise "
                         "legacy path")
    ap.add_argument("--trim-k", type=int, default=0,
                    help="values trimmed per side for --robust-agg "
                         "trimmed (needs 2k < sparsity)")
    ap.add_argument("--adversary", default="none",
                    choices=["none", "sign_flip", "scale", "inlier"],
                    help="simulate a Byzantine fraction of clients "
                         "(deterministic in --seed): sign-flipped, "
                         "scaled, or collusive-inlier uplinks")
    ap.add_argument("--f-byz", type=float, default=0.0,
                    help="Byzantine client fraction for --adversary")
    ap.add_argument("--reputation", action="store_true",
                    help="EWMA anomaly reputation driving escalating "
                         "quarantine windows (needs --adversary; fused "
                         "synchronous driver only)")
    args = ap.parse_args(argv)

    n_dev = args.data_parallel * args.model_parallel
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={n_dev}"
    )

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import checkpoint, metrics, spans
    from repro.configs import registry
    from repro.data import DataConfig, SyntheticTokenPipeline, device_sampler
    from repro.dist import comm_ws, rounds, sharding, tamuna_dp
    from repro.launch.mesh import make_host_mesh
    from repro.launch.runtime import device_info, enable_compile_cache

    enable_compile_cache()
    dev = device_info()
    print(f"[train] device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}")
    mesh = make_host_mesh(args.data_parallel, args.model_parallel)
    cfg = (
        registry.get_reduced_config(args.arch)
        if args.reduced else registry.get_config(args.arch)
    )
    n = args.clients or sharding.n_clients(mesh)
    # partial participation works on BOTH uplinks now (the blocked bands
    # lie over the cohort slots, DESIGN.md §11) — no c = n forcing
    c = args.cohort or max(2, (3 * n) // 4)
    tcfg = tamuna_dp.DistTamunaConfig(
        gamma=args.gamma, c=c, s=min(args.sparsity, c), p=args.p,
        uplink=args.uplink, comm_impl=args.comm_impl,
        wire_precision=args.wire_precision, wire_down=args.wire_down,
        robust_agg=args.robust_agg, trim_k=args.trim_k,
    )
    print(f"[train] comm impl: "
          f"{comm_ws.effective_impl(tcfg.comm_impl, meshed=True, mesh=mesh)}")
    adversarial = args.adversary != "none" and args.f_byz > 0.0
    if args.reputation and not adversarial:
        ap.error("--reputation needs --adversary and --f-byz > 0")
    if adversarial and (args.no_fuse or args.pipeline):
        ap.error("--adversary runs on the fused synchronous driver "
                 "(drop --no-fuse/--pipeline)")
    warm = _warm_lengths(args.max_L)
    if args.profile_dir and (args.no_fuse or args.pipeline
                             or args.rounds <= len(warm)):
        ap.error(f"--profile-dir traces the fused synchronous rounds "
                 f"(drop --no-fuse/--pipeline) after {len(warm)} "
                 f"warm rounds (raise --rounds)")

    state = tamuna_dp.init_state(jax.random.key(args.seed), cfg, mesh,
                                 tcfg, n=n)
    specs = tamuna_dp.state_pspecs(state, cfg, mesh)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    state = jax.device_put(state, shardings)

    pipe = SyntheticTokenPipeline(
        DataConfig(
            seq_len=args.seq_len, per_client_batch=args.per_client_batch,
            vocab=min(cfg.vocab, 512), seed=args.seed, n_clients=n,
        ),
        cfg, mesh,
    )

    logger = metrics.MetricLogger(args.log or None)
    rng = np.random.default_rng(args.seed)
    t0 = time.time()

    if args.no_fuse:
        # legacy per-step path: one dispatch per local step, host batches —
        # but with the state buffers donated (the seed copied the full
        # (n, *param) state in HBM every step).  Cohort-aware too: at
        # c < n only the cohort's rows are gathered, trained, and
        # scattered back (idle clients do nothing; the DownCom broadcasts
        # here — the per-step escape hatch keeps the simpler eager form).
        local_step = jax.jit(
            jax.named_scope(spans.LOCAL_STEP)(
                tamuna_dp.make_local_step(cfg, tcfg)),
            donate_argnums=(0,),
        )
        comm_step = jax.jit(
            jax.named_scope(spans.COMM_STEP)(
                tamuna_dp.make_comm_step(cfg, tcfg, mesh, n=n)),
            donate_argnums=(0,),
        )
        key = jax.random.key(args.seed + 1)
        total_steps = 0
        final_loss = float("nan")
        # same elasticity gate as the fused engine: gather only where
        # cohort rows can vacate hardware
        elastic = rounds.default_elastic(
            n, tcfg.c, sharding.n_clients(mesh)
        )
        for r in range(args.rounds):
            L = tamuna_dp.sample_round_length(rng, tcfg.p, max_L=args.max_L)
            key, ck = jax.random.split(key)
            cohort = (tamuna_dp.round_cohort(ck, n, tcfg.c)
                      if elastic else None)
            work = (tamuna_dp.gather_cohort(state, cohort)
                    if elastic else state)
            for _ in range(L):
                batch = pipe.next_batch(
                    clients=np.asarray(cohort) if elastic else None
                )
                work, m = local_step(work, **batch)
                total_steps += 1
            if elastic:
                # the gather SHARED the scalar leaves (round / float
                # accumulators / opt.count) with `state`, and the first
                # donated local_step deleted those buffers — rebuild them
                # from `work`, whose leaves are live donated-jit outputs
                # (local steps never change their values)
                state = tamuna_dp.scatter_cohort(
                    state, work, cohort
                )._replace(
                    round=work.round, up_floats=work.up_floats,
                    down_floats=work.down_floats,
                    up_bytes=work.up_bytes, down_bytes=work.down_bytes,
                )
            else:
                state = work
            state = comm_step(state, jax.random.key_data(ck),
                              cohort=cohort)
            final_loss = float(m["loss"])
            logger.log(r, {
                "round": r, "L": L, "loss": final_loss,
                "local_steps": total_steps,
            })
            if (args.checkpoint_dir and args.checkpoint_every
                    and (r + 1) % args.checkpoint_every == 0):
                checkpoint.save(
                    os.path.join(args.checkpoint_dir, f"step_{r+1}"),
                    state, r + 1,
                )
    elif args.pipeline:
        from repro.dist import faults as faults_mod

        latency = (faults_mod.EmpiricalDelays.from_json(
            args.latency_dist, n=n, seed=args.seed,
        ) if args.latency_dist else None)
        engine = rounds.make_pipelined_round_fn(
            cfg, tcfg, mesh,
            sample_batch=device_sampler(pipe.dcfg, cfg, mesh),
            max_L=args.max_L, n=n,
        )
        state, last = rounds.run_rounds_pipelined(
            state,
            round_fn=engine,
            data=pipe.device_data(),
            key=jax.random.key(args.seed + 1),
            rounds=args.rounds,
            rng=rng,
            p=tcfg.p,
            staleness=args.staleness,
            flush_every=args.flush_every,
            logger=logger,
            checkpoint_dir=args.checkpoint_dir or None,
            checkpoint_every=args.checkpoint_every,
            latency=latency,
            policy=args.round_policy,
            quorum=args.quorum or None,
        )
        total_steps = last.get("local_steps", 0)
        final_loss = last.get("loss", float("nan"))
        if "commit_s" in last:
            print(f"[train] simulated clock: {last['commit_s']:.2f}s "
                  f"at staleness {args.staleness}")
    else:
        round_fn = rounds.make_round_fn(
            cfg, tcfg, mesh,
            sample_batch=device_sampler(pipe.dcfg, cfg, mesh),
            max_L=args.max_L, n=n,
        )
        fkw = {}
        if adversarial:
            from repro.dist import cohort as cohort_mod
            from repro.dist import faults as faults_mod

            fkw["faults"] = faults_mod.FaultPlan(
                seed=args.seed, n=n,
                model=faults_mod.FaultModel(
                    adversary=args.adversary, f_byz=args.f_byz,
                ),
            )
            if args.reputation:
                fkw["plan"] = cohort_mod.CohortPlan(args.seed, n, tcfg.c)
                fkw["reputation"] = True
        lengths = (_ProfiledLengths(rng, warm, args.profile_dir)
                   if args.profile_dir else rng)
        try:
            if args.profile_dir:
                lengths.start()
            # eager arrays (a fresh carry's counters, the trace reset) take
            # the mesh's sharding, as the round programs' outputs do, so a
            # trace reset gives no program another signature
            with jax.set_mesh(mesh):
                state, last = rounds.run_rounds(
                    state,
                    round_fn=round_fn,
                    data=pipe.device_data(),
                    key=jax.random.key(args.seed + 1),
                    rounds=args.rounds,
                    rng=lengths,
                    p=tcfg.p,
                    flush_every=args.flush_every,
                    logger=logger,
                    checkpoint_dir=args.checkpoint_dir or None,
                    checkpoint_every=args.checkpoint_every,
                    **fkw,
                )
            if args.profile_dir:
                jax.block_until_ready(state)
        finally:
            if args.profile_dir:
                lengths.close()
        if args.profile_dir:
            print(f"[train] profiles of set-up (rounds 0.."
                  f"{len(warm) - 1}) and of rounds {len(warm)}.."
                  f"{args.rounds - 1} in {args.profile_dir}")
        total_steps = last.get("local_steps", 0)
        final_loss = last.get("loss", float("nan"))
        if round_fn_hook is not None:
            round_fn_hook(round_fn)

    logger.close()
    dt = time.time() - t0
    print(f"[train] {args.rounds} rounds / {total_steps} local steps "
          f"in {dt:.1f}s; final loss {final_loss:.4f}")
    return 0


def _warm_lengths(max_L: int) -> list:
    """Round lengths that call each bucket program after each one (and
    the first after a fresh carry): a program's outputs need not share
    its inputs' shardings, so a program compiles once for each program
    whose carry it takes.  The pairs of k buckets follow each other in
    k*k + 1 rounds (the prefer-largest de Bruijn sequence)."""
    buckets = [1 << b for b in range(max_L.bit_length())]
    seq, used = [0], set()
    while True:
        nxt = [b for b in reversed(range(len(buckets)))
               if (seq[-1], b) not in used]
        if not nxt:
            return [buckets[b] for b in seq]
        used.add((seq[-1], nxt[0]))
        seq.append(nxt[0])


class _ProfiledLengths:
    """The round lengths of a ``--profile-dir`` run, and its two traces.

    The first rounds take the ``warm`` lengths (``_warm_lengths``), so
    every round program compiles in them for every carry it meets; the
    later rounds take ``rng``'s geometric draws.  ``start`` opens
    ``<dir>/setup``, which holds the fresh carry and the warm rounds,
    each program's first call under its ``tamuna.compile.<bucket>``
    span.  ``run_rounds`` draws a round's length before it calls the
    round's program, so the first geometric draw closes it and opens
    ``<dir>/rounds``, which holds the rest: the round of that draw has
    its program calls there and its ``tamuna.round`` span in neither
    trace.  ``close`` ends the open trace."""

    def __init__(self, rng, warm, profile_dir: str):
        self.rng, self.warm, self.dir = rng, list(warm), profile_dir
        self.phase = None

    def _switch(self, phase) -> None:
        import jax

        if self.phase is not None:
            jax.profiler.stop_trace()
        self.phase = phase
        if phase is not None:
            # host spans and JAX's own annotations (its lowering and
            # compile among them), not every Python call
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(os.path.join(self.dir, phase),
                                     profiler_options=opts)

    def start(self) -> None:
        self._switch("setup")

    def geometric(self, p: float) -> int:
        if self.warm:
            return self.warm.pop(0)
        if self.phase == "setup":
            self._switch("rounds")
        return int(self.rng.geometric(p))

    def close(self) -> None:
        self._switch(None)


if __name__ == "__main__":
    sys.exit(main())
