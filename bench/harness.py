"""One run of one cell: set-up, the measured window, the check against the
reference, and (traced) the per-layer metrics.

Everything a cell needs is found by name: ``BENCHMARK.json`` at the root
names the cell's configuration and metrics, ``bench/workloads/<cell>.json``
holds its traffic and settings, ``bench/configs/<config>.json`` its sizes
(with the plain reference and FLOP count in ``<config>.py`` beside it),
and ``bench/metrics/<metric>.py`` the reader of each per-layer metric.
"""

from __future__ import annotations

import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def workload(name: str, rehearse: bool = False) -> dict:
    wl = load_json(BENCH, "workloads", f"{name}.json")
    if rehearse:
        wl = {**wl, **wl.get("rehearse", {})}
    return wl


def model_config(name: str, rehearse: bool = False):
    """The ``ModelConfig`` a configuration file describes."""
    import jax.numpy as jnp

    from repro.models.transformer import ModelConfig

    doc = load_json(BENCH, "configs", f"{name}.json")
    if rehearse:
        doc = {**doc, **doc.get("rehearse", {})}
    fields = {k: v for k, v in doc.items()
              if k in ModelConfig.__dataclass_fields__}
    for k in ("dtype", "param_dtype"):
        fields[k] = getattr(jnp, fields[k])
    return ModelConfig(**{**fields, "name": doc["name"]})


def config_module(name: str):
    return importlib.import_module(f"bench.configs.{name}")


def metric_reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}").read


def round_buckets(max_L: int) -> List[int]:
    """Set-up's round lengths: the three rounds the reference follows
    (1, 2, 1 local steps; 1, 1, 1 where rounds are one step long), then a
    round of each bucket program not yet run (4, 8, ... up to
    ``max_L``), so set-up runs every program the window calls."""
    first = [1, 2, 1] if max_L >= 2 else [1, 1, 1]
    return first + [1 << b for b in range(2, int(max_L).bit_length())]


def eta_of(p: float, n: int, s: int) -> float:
    """TAMUNA's control stepsize, Remark 2: p n (s - 1) / (s (n - 1))."""
    n = max(n, 2)
    return p * n * (s - 1) / (s * (n - 1))


class Rows:
    """Collects the rows ``run_rounds`` drains (its logger)."""

    def __init__(self):
        self.rows: List[dict] = []

    def log(self, step, row):
        self.rows.append(dict(row))


@dataclass
class Run:
    """What the per-layer readers see of one run."""
    cell: str
    wl: dict
    cfg: Any
    config: Any  # the configuration's module: flops_per_sample
    chips: int
    peaks: dict
    window_s: float
    rounds: int
    local_steps: int  # local steps of the window (per client)
    samples: int  # client samples trained in the window
    tokens: int
    setup: dict  # the compile clock over set-up
    state_dims: List[int] = field(default_factory=list)
    trace: Any = None  # bench.trace.Summary of the traced window


class NoChip(RuntimeError):
    pass


def device_check(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def peaks_for(kind: str, rehearse: bool) -> dict:
    table = load_json(BENCH, "peaks.json")["devices"]
    if rehearse:
        # the CPU has no entry; the rehearsal prints no result
        return next(iter(table.values()))
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


class Cell:
    """A cell's engine, built once per process: its configuration, mesh
    and round programs, and the seed-driven makers of its state and
    data, so one process can run many seeds on the same programs."""

    def __init__(self, name: str, rehearse: bool = False):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from bench import traffic, weights
        from repro.dist import model_api, rounds, sharding, tamuna_dp
        from repro.launch.mesh import make_host_mesh

        self.name, self.rehearse = name, rehearse
        self.spec = next(w for w in benchmark()["workloads"]
                         if w["name"] == name)
        self.wl = wl = workload(name, rehearse)
        self.cfg = cfg = model_config(self.spec["config"], rehearse)
        self.cmod = config_module(self.spec["config"])
        self.devs = device_check(self.spec["chips"], rehearse)
        self.kind = self.devs[0].device_kind
        self.peaks = peaks_for(self.kind, rehearse)
        self.mesh = mesh = make_host_mesh(*wl["mesh"])
        self.n, self.c, self.s, self.p = n, c, s, p = (
            wl["clients"], wl["cohort"], wl["sparsity"], wl["p"])
        self.gamma = wl["gamma"]
        self.eta = eta_of(p, n, s)
        tcfg = tamuna_dp.DistTamunaConfig(
            gamma=self.gamma, c=c, s=s, p=p, eta=self.eta,
            uplink=wl["uplink"], comm_impl="auto", wire_precision="f32")
        self.struct = struct = jax.eval_shape(
            lambda: model_api.init(jax.random.key(0), cfg))
        self.dims = [int(math.prod(a.shape))
                     for a in jax.tree.leaves(struct)]

        def make_state(key):
            x0 = weights.make_params(key, struct)
            return tamuna_dp.DistTamunaState(
                x=jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), x0),
                h=jax.tree.map(
                    lambda a: jnp.zeros((n,) + a.shape, jnp.float32), x0),
                opt=(), round=jnp.zeros((), jnp.int32),
                up_floats=jnp.zeros((), jnp.float32),
                down_floats=jnp.zeros((), jnp.float32),
                up_bytes=jnp.zeros((), jnp.float32),
                down_bytes=jnp.zeros((), jnp.float32))

        key_struct = jax.eval_shape(lambda: jax.random.key(0))
        specs = tamuna_dp.state_pspecs(
            jax.eval_shape(make_state, key_struct), cfg, mesh)
        shard = jax.tree.map(lambda q: NamedSharding(mesh, q), specs,
                             is_leaf=lambda q: isinstance(q, P))
        dp = sharding.dp_axes(mesh)
        self.ids = ids = min(wl["token_ids"], cfg.vocab)
        self.make_state = jax.jit(make_state, out_shardings=shard)
        self.make_x0 = jax.jit(lambda k: weights.make_params(k, struct),
                               out_shardings=NamedSharding(mesh, P()))
        self.make_data = jax.jit(
            lambda k: traffic.bigram_tables(k, n, ids),
            out_shardings={"cum": NamedSharding(mesh, P(dp))})
        self.round_fn = rounds.make_round_fn(
            cfg, tcfg, mesh, sample_batch=traffic.sampler(wl, cfg, mesh, dp),
            max_L=wl["max_L"], n=n)
        self.lengths = round_buckets(wl["max_L"])
        log(f"cell {name}: n={n} c={c} s={s} p={p} gamma={self.gamma} "
            f"eta={self.eta:.6g} uplink={wl['uplink']} "
            f"elastic={self.round_fn.elastic} mesh={wl['mesh']} "
            f"devices={len(self.devs)} x {self.kind}")

    def first_rounds(self, seed: int):
        """State and data from the seed, driven through the first rounds
        (one of each bucket program, so set-up warms every program the
        window calls).  Returns ``(state, data, readings)``; call inside
        ``jax.set_mesh(self.mesh)``."""
        import jax

        from bench import correct, traffic
        from repro.dist import rounds

        k_w = traffic.seed_key(seed, 0)
        state = self.make_state(k_w)
        data = self.make_data(traffic.seed_key(seed, 1))
        x0 = self.make_x0(k_w)
        # keys enter ``run_rounds`` as raw key data, in set-up as in the
        # window, so the window runs no eager op set-up has not compiled
        carry = rounds.init_carry(state, traffic.key_data(seed, 2),
                                  self.wl["flush_every"])
        norms = {}
        for r, L in enumerate(self.lengths):
            carry = self.round_fn(carry, data, L, r)
            if r + 1 in (1, 3):
                norms[r + 1] = [float(v) for v in
                                correct.leaf_norms(carry.state.x, x0)]
        tr = jax.device_get(carry.traces)
        losses = [float(tr["loss_sum"][r]) / int(tr["steps"][r])
                  for r in range(3)]
        return carry.state, data, correct.readings(
            losses, norms[1], norms[3], self.gamma)

    def reference(self, seed: int, quant: str = "exact",
                  fault: Optional[str] = None) -> dict:
        """The reference's readings of the first three rounds, on one
        device, from the seed alone; ``quant="fp8"`` is the control.
        ``fault`` plants one in the reference's local step, to read what
        it does at the cell's size: ``half_batch`` (each client's loss
        over the first half of its batch) or ``token`` (every target token
        of each client's first sequence altered)."""
        import jax

        from bench import correct, ref_layers, reference, traffic, weights

        n, cfg, wl, struct = self.n, self.cfg, self.wl, self.struct
        with jax.default_device(self.devs[0]):
            x0 = jax.jit(lambda k: weights.make_params(k, struct))(
                traffic.seed_key(seed, 0))
            data = jax.jit(lambda k: traffic.bigram_tables(k, n, self.ids))(
                traffic.seed_key(seed, 1))
            q = ref_layers.QUANT[quant]

            def loss_fn(prm, b):
                if fault == "half_batch":
                    b = {k: v[: v.shape[0] // 2] for k, v in b.items()}
                elif fault == "token":
                    lab = b["labels"]
                    b = {**b, "labels": lab.at[0].set(
                        (lab[0] + 1) % self.ids)}
                return self.cmod.loss(prm, b, cfg, q)

            losses, norms = reference.run_rounds(
                x0=x0, n=n, loss_fn=loss_fn,
                sample=jax.jit(traffic.sampler(wl, cfg)), data=data,
                key0=jax.random.wrap_key_data(traffic.key_data(seed, 2)),
                lengths=self.lengths[:3], c=self.c, s=self.s,
                uplink=wl["uplink"], gamma=self.gamma, eta=self.eta,
                norms_after=(1, 3))
        return correct.readings(losses, norms[1], norms[3], self.gamma)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, rehearse: bool = False) -> dict:
    import jax
    import numpy as np

    from bench import clock as clock_mod
    from bench import correct, traffic
    from repro.dist import rounds

    clock = clock_mod.CompileClock()
    cell = Cell(name, rehearse)
    wl, c = cell.wl, cell.c
    # eager arrays ``run_rounds`` makes (a fresh carry's counters) take the
    # mesh's sharding, as the round programs' outputs do, so a slice's
    # first round calls the same program as its later rounds
    with jax.set_mesh(cell.mesh):
        with jax.profiler.TraceAnnotation("bench.warmup"):
            state, data, prog = cell.first_rounds(seed)
            jax.block_until_ready(state)
        setup = clock.snapshot()
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s:.3f} s: compile {setup['compile_s']:.3f} s, "
            f"{setup['programs']} programs, {setup['cache_hits']} cache "
            f"hits; first-round losses {prog['loss']}")

        # ---- the measured window ----------------------------------------
        sched = traffic.LengthSchedule(wl["round_lengths"], seed)
        k_win = traffic.key_data(seed, 3, count=4096)
        rows = Rows()
        flush = wl["flush_every"]
        # the clock is read only between whole blocks of round lengths, so
        # every window, however fast, trains the cell's stated L mix
        per_check = math.lcm(len(sched.block), flush)
        tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        clock.reset()
        if trace:
            jax.profiler.start_trace(tdir)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            i = 0
            while time.perf_counter() - t0 < seconds:
                with jax.profiler.TraceAnnotation("bench.rounds"):
                    state, _ = rounds.run_rounds(
                        state, round_fn=cell.round_fn, data=data,
                        key=k_win[i], rounds=per_check, rng=sched, p=cell.p,
                        flush_every=flush, logger=rows, max_L=wl["max_L"])
                i += 1
            with jax.profiler.TraceAnnotation("bench.drain"):
                jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
        if trace:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"profiler stopped in {time.perf_counter() - t_stop:.3f} s")
        in_window = clock.snapshot()

    peak = clock_mod.peak_bytes(cell.devs)
    log(f"device memory stats: {cell.devs[0].memory_stats()}")
    for a in jax.tree.leaves((state, data)):
        a.delete()
    del state, data
    if trace or (setup["cache_hits"] == 0 and not rehearse):
        # the programs' name stacks, which the trace's op events lack,
        # from the programs compiled again from their lowering (after the
        # peak is read).  The compile cache keys these apart from the jit
        # calls' entries, so a checkout's first run, which compiles
        # anyway, puts them there for the traced runs to load.
        from bench import trace as trace_mod

        t_names = time.perf_counter()
        progs = {B: low.compile()
                 for B, low in cell.round_fn.lowered().items()}
        names = trace_mod.op_names(p.as_text() for p in progs.values())
        log(f"programs' text in {time.perf_counter() - t_names:.3f} s")
        for B, p in progs.items():
            ma = p.memory_analysis()
            if ma is not None:
                log(f"program {B}: arguments {ma.argument_size_in_bytes} B, "
                    f"temporaries {ma.temp_size_in_bytes} B, outputs "
                    f"{ma.output_size_in_bytes} B, aliased "
                    f"{ma.alias_size_in_bytes} B")
        del progs
    cell.round_fn.cache.clear()

    Ls = [int(r["L"]) for r in rows.rows]
    losses = [float(r["loss"]) for r in rows.rows]
    failed = sum(1 for v in losses if not math.isfinite(v))
    samples = sum(Ls) * c * wl["per_client_batch"]
    run = Run(cell=name, wl=wl, cfg=cell.cfg, config=cell.cmod,
              chips=len(cell.devs), peaks=cell.peaks, window_s=window_s,
              rounds=len(Ls), local_steps=sum(Ls), samples=samples,
              tokens=samples * wl["seq_len"], setup=setup,
              state_dims=cell.dims)
    up = np.diff([0.0] + [r["up_bytes"] for r in rows.rows])
    down = np.diff([0.0] + [r["down_bytes"] for r in rows.rows])
    hist = {L: Ls.count(L) for L in sorted(set(Ls))}
    log(f"window {window_s:.3f} s: {run.rounds} rounds, {run.local_steps} "
        f"local steps, L histogram {hist}, {in_window['programs']} programs "
        f"compiled, {in_window['cache_hits']} cache hits")
    if len(up):
        log(f"wire per round and client: up {float(np.median(up)):.0f} B, "
            f"down {float(np.median(down)):.0f} B")
    if losses:
        log(f"window losses: first {losses[0]:.5f} last {losses[-1]:.5f} "
            f"min {min(losses):.5f} max {max(losses):.5f}")

    # ---- the check against the reference --------------------------------
    t_ref = time.perf_counter()
    clock.reset()
    ref = cell.reference(seed)
    g = correct.gaps(prog, ref)
    log(f"reference {time.perf_counter() - t_ref:.3f} s (compile "
        f"{clock.snapshot()['compile_s']:.3f} s); losses {ref['loss']}")
    limits = wl["limits"]
    compiles = in_window["programs"] + in_window["lowerings"]
    ok = correct.judge(g, limits) and compiles == 0 and failed == 0 and \
        run.rounds > 0
    checks = correct.report(g, limits)
    checks["window_compiles"] = {"value": compiles, "limit": 0}

    metrics = {}
    bm = benchmark()
    if trace:
        t_tr = time.perf_counter()
        run.trace = trace_mod.summarize(trace_mod.load(tdir), names,
                                        chips=1 if rehearse else run.chips)
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t_tr:.3f} s")
        log(f"trace: busy {run.trace.busy_s:.6f} s of "
            f"{run.trace.window_s:.6f} s; local {run.trace.cat_s['local']:.6f}"
            f" comm {run.trace.cat_s['comm']:.6f} other "
            f"{run.trace.cat_s['other']:.6f} (unnamed "
            f"{run.trace.unnamed_s:.6f}) remainder "
            f"{run.trace.remainder_s:.3e} s")
        if not rehearse:
            # the CPU's op events are thunk names, not HLO text: a
            # rehearsal reads its trace for control flow only
            run.trace.check()
        for m in bm["per_layer"]:
            if name not in m.get("workloads", [name]):
                continue
            v = metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"train_tokens_per_s": run.tokens / window_s,
               "peak_hbm_gib": peak / 2 ** 30, "setup_s": setup_s}
        for m in bm["end_to_end"]:
            if name in m.get("workloads", [name]):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": cell.devs[0].platform, "kind": cell.kind,
              "count": run.chips, "memory_peak_bytes": peak}
    out = {"correct": bool(ok), "attempted": run.rounds, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = checks
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} limit {v['limit']!r}")
    return out
