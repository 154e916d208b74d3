#!/usr/bin/env python3
"""Bring-up check of the TAMUNA trainer on TPU, through its normal entry
point (``repro.launch.train`` -> ``rounds.run_rounds`` -> ``tamuna_dp`` ->
``comm_ws`` with the Pallas kernels), at the published width of
``whisper-tiny``.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # the four-chip host: sharded paths only

One chip runs three phases in this one process:

  (a) device check: prints ``jax.devices()``; anything but a TPU exits 1;
  (b) every kernel the TPU dispatch selects, compiled by Mosaic (never the
      interpreter), and the int wire's shared dequant ahead of them, at
      n=8 client rows and the whisper-tiny client width, against their
      ``repro.kernels.ref`` oracles;
  (c) whisper-tiny training through ``repro.launch.train.main``: the
      cyclic uplink, the blocked uplink, and the int8 wire with a trimmed
      combiner, each with ``--comm-impl auto`` (which must resolve to
      ``pallas``) and with ``--comm-impl dense``; losses must be finite,
      the two impls' per-round losses must agree, and every round
      program the auto run compiled (read back through ``train.main``'s
      ``round_fn_hook``) must hold a Mosaic kernel (``tpu_custom_call``).
      Every run takes 4 rounds; the cyclic pair draws round lengths up to
      4 local steps (two round programs to compile), the others one step
      per round (one program), which keeps a cold run's compiles well
      inside a 20-minute budget.

``--chips 4`` runs only phase (c)'s cyclic pair on a (4, 1) and on a (2, 2)
``(data, model)`` mesh.  Every run prints its wall time, its compile time
(set-up), the peak device memory so far and the comm impl it resolved.
The last stdout line is ``{"ok": true, "device": {...}}`` and appears only
when every phase passed.  Compiles go to JAX's persistent cache
(``repro.launch.runtime.enable_compile_cache``), so a second run in the
same place loads them.

``--rehearse`` runs the same phases on the CPU at a tiny size (reduced
config, interpret-mode kernels); it checks control flow only and never
prints the result line.  With ``--chips 4`` it needs four virtual devices,
which it asks XLA for itself.
"""

from __future__ import annotations

import argparse
import csv
import functools
import gc
import json
import math
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# whisper-tiny's client width: the total parameter count of one client row
# (registry.get_config("whisper-tiny"), rounded)
CHIP_D = 36_500_000
REHEARSAL_D = 70_000 + 123  # ragged: a partial last block

# |kernel - oracle| bounds, by what the two sides compute.  Sums of <= 8
# f32 terms differ by reassociation (Mosaic reduces tiles, XLA fuses):
# a few ulp of values ~N(0, 1).  Selections (x_new, counts) are exact.
# fused_local_step stores x - gamma*(g - h) in bf16, the oracle
# x - gamma*g + gamma*h: the two f32 values differ by a few f32 ulp of
# the operands (~1e-7 for values ~N(0, 1)), so their bf16 roundings may
# differ by one bf16 ulp (2^-7 of the value) and, where the sum cancels
# to near zero, by that f32 difference itself.
TOL_SUM = 1e-5
TOL_BF16_REL = 2.0 ** -7
TOL_STEP_ABS = 1e-6
# |loss(auto) - loss(dense)| per round (losses ~10): the two impls differ
# only in the comm step's float roundoff (the kernels reassociate the owner
# sums), which the bf16 parameter storage can turn into one bf16 ulp on a
# few coordinates.  Calibrated on a v5e with these runs' shapes: sound
# gaps reached 1.34e-4 (the (2, 2) mesh); a comm step that skips one
# client row's DownCom moved the block_rs losses by 8.3e-3 and a 1/(s+1)
# rebuild by 0.10.  A fault that only touches h (one row's control
# variate left stale) leaves 4 rounds of loss unchanged: the kernel
# phase's exact h_update check is what catches it.
TOL_LOSS = 1e-3


def options(argv) -> dict:
    """``--flag value`` pairs of a train argv (bare flags map to True)."""
    out, i = {}, 0
    while i < len(argv):
        if i + 1 < len(argv) and not argv[i + 1].startswith("--"):
            out[argv[i]] = argv[i + 1]
            i += 2
        else:
            out[argv[i]] = True
            i += 1
    return out


def check(failures, name, err, tol):
    ok = bool(err <= tol)
    print(f"[smoke] kernel {name}: max_err={err!r} tol={tol!r} "
          f"{'PASS' if ok else 'FAIL'}", flush=True)
    if not ok:
        failures.append(f"kernel {name}")


def kernel_phase(d: int, interpret: bool, failures, clock) -> None:
    """Each TPU-dispatched kernel against its oracle at n=8 rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.dist import wire
    from repro.kernels import compress, local_step, ref, uplink

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(seed, rows, cols):
        """U[-2, 2) from the wire's counter hash: an elementwise program
        (jax.random at these widths takes ~15 s per shape to compile)."""
        return 4.0 * wire.uniform01(
            seed, jnp.arange(rows)[:, None], jnp.arange(cols)[None, :]
        ) - 2.0

    n, m, s = 8, 6, 3  # cohort of 6 template columns, 2 idle rows
    x, h = draw(1, n, d), draw(2, n, d)
    x_bar = draw(3, 1, d)[0]
    slot = jnp.asarray(np.r_[np.random.default_rng(0).permutation(m),
                             [-1, -1]], jnp.int32)
    band = compress.cyclic_band(jnp.arange(d, dtype=jnp.int32), m, s)
    down = jnp.asarray([1, 1, 1, 0, 1, 0, 1, 1], jnp.int32)
    covered = (jnp.arange(d) % 7) != 3
    nc = -(-d // 256)
    codes = (draw(4, n, d) * 63.5).astype(jnp.int8)
    scales = (draw(5, n, nc) + 2.0) / (4 * 127)
    chunk = jnp.arange(d, dtype=jnp.int32) // 256

    @jax.jit
    def excess(a, b, rel):
        """max(|a - b| - rel * |b|): the error beyond a relative bound."""
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.max(jnp.abs(a - b) - rel * jnp.abs(b))

    def maxerr(a, b, rel=0.0):
        return float(excess(a, b, rel))

    def timed(name, fn, *args):
        clock.reset()
        t0 = time.perf_counter()
        out = jax.block_until_ready(jax.jit(fn)(*args))
        print(f"[smoke] kernel {name}: first call "
              f"{time.perf_counter() - t0:.3f}s (compile {clock.secs:.3f}s)",
              flush=True)
        return out

    kw = dict(interpret=interpret)
    got = timed("masked_sum", lambda x, sl, b: uplink.masked_sum(
        x, sl, b, m, s, **kw), x, slot, band)
    want = timed("oracle masked_sum", lambda x, sl, b:
                 ref.uplink_masked_sum_ref(x, sl, b, m, s), x, slot, band)
    check(failures, "masked_sum", maxerr(got, want), TOL_SUM)

    gn, gc_ = timed("masked_sum(counts)", lambda x, sl, b: uplink.masked_sum(
        x, sl, b, m, s, counts=True, **kw), x, slot, band)
    wn, wc = timed("oracle masked_sum(counts)", lambda x, sl, b:
                   ref.uplink_masked_sum_ref(x, sl, b, m, s, counts=True),
                   x, slot, band)
    check(failures, "masked_sum(counts).num", maxerr(gn, wn), TOL_SUM)
    check(failures, "masked_sum(counts).cnt", maxerr(gc_, wc), 0.0)

    # the int wire: codes through the shared dequant, then the float kernel
    vals = timed("wire_dequant", compress.wire_dequant, codes, scales, chunk)
    want = timed("oracle wire_dequant", ref.wire_dequant_ref, codes, scales)
    check(failures, "wire_dequant", maxerr(vals, want), 0.0)
    for counts in (False, True):
        tag = "int-wire masked_sum" + ("(counts)" if counts else "")
        got = timed(tag, lambda v, sl, b: uplink.masked_sum(
            v, sl, b, m, s, counts=counts, **kw), vals, slot, band)
        want = timed("oracle " + tag, lambda v, sl, b:
                     ref.uplink_masked_sum_ref(ref.wire_dequant_ref(*v), sl,
                                               b, m, s, counts=counts),
                     (codes, scales), slot, band)
        if counts:
            check(failures, tag + ".num", maxerr(got[0], want[0]), TOL_SUM)
            check(failures, tag + ".cnt", maxerr(got[1], want[1]), 0.0)
        else:
            check(failures, tag, maxerr(got, want), TOL_SUM)
    del vals, codes, scales, chunk, got, want

    for kind, k in (("trimmed", 1), ("median", 0)):
        tag = f"robust_sum({kind})"
        gb, gcnt = timed(tag, lambda x, sl, b: uplink.robust_sum(
            x, sl, b, m, s, kind=kind, k=k, **kw), x, slot, band)
        wb, wcnt = timed("oracle " + tag, lambda x, sl, b:
                         ref.uplink_robust_sum_ref(x, sl, b, m, s, kind, k),
                         x, slot, band)
        check(failures, tag + ".bar", maxerr(gb, wb), TOL_SUM)
        check(failures, tag + ".cnt", maxerr(gcnt, wcnt), 0.0)
        del gb, gcnt, wb, wcnt

    for cov in (None, covered):
        tag = "h_update(down" + (", covered)" if cov is not None else ")")
        gh, gx = timed(tag, lambda x, h, xb, sl, b, dn, cv: uplink.h_update(
            x, h, xb, sl, b, m, s, 0.5, down=dn, covered=cv, **kw),
            x, h, x_bar, slot, band, down, cov)
        wh, wx = timed("oracle " + tag,
                       lambda x, h, xb, sl, b, dn, cv: ref.uplink_h_update_ref(
                           x, h, xb, sl, b, m, s, 0.5, down=dn, covered=cv),
                       x, h, x_bar, slot, band, down, cov)
        check(failures, tag + ".h", maxerr(gh, wh), TOL_SUM)
        check(failures, tag + ".x", maxerr(gx, wx), 0.0)
        del gh, gx, wh, wx

    xs = x[0].astype(jnp.bfloat16)
    got = timed("fused_local_step",
                lambda x, g, h: local_step.fused_local_step(
                    x, g, h, 0.05, **kw), xs, h[0], h[1])
    want = timed("oracle fused_local_step", lambda x, g, h:
                 ref.fused_local_step_ref(x, g, h, 0.05), xs, h[0], h[1])
    print(f"[smoke] kernel fused_local_step: max |err| "
          f"{maxerr(got, want)!r}", flush=True)
    check(failures, "fused_local_step(beyond 1 bf16 ulp)",
          maxerr(got, want, TOL_BF16_REL), TOL_STEP_ABS)


COLLECTIVES = ("stablehlo.all_reduce", "stablehlo.all_gather",
               "stablehlo.reduce_scatter", "stablehlo.all_to_all",
               "stablehlo.collective_permute")


def program_ops(round_fn) -> dict:
    """Mosaic kernel calls (``tpu_custom_call``) and collectives in each
    program a ``train.main`` run compiled, by its chunk length B: its
    ``round_fn.lowered()`` StableHLO, counted by op name."""
    from jaxlib.mlir import ir

    out = {}
    for key, low in sorted(round_fn.lowered().items(), key=str):
        found: dict = {}

        def visit(op, found=found):
            name = op.name
            if name == "stablehlo.custom_call":
                name = ir.StringAttr(
                    op.attributes["call_target_name"]).value
            if name == "tpu_custom_call" or name in COLLECTIVES:
                found[name] = found.get(name, 0) + 1
            return ir.WalkResult.ADVANCE

        low.compiler_ir("stablehlo").operation.walk(visit)
        out[f"B={key[0]}"] = found
    return out


def train_run(name, argv, dp, mp, clock, tmp, failures, *, want_impl=None,
              on_tpu=True):
    """One ``train.main`` run; returns its per-round losses."""
    import jax

    from bench.clock import peak_bytes
    from repro.dist import comm_ws
    from repro.launch import train
    from repro.launch.mesh import make_host_mesh

    log = os.path.join(tmp, f"{name}.csv")
    argv = argv + ["--data-parallel", str(dp), "--model-parallel", str(mp),
                   "--log", log]
    opt = options(argv)
    impl = comm_ws.effective_impl(opt["--comm-impl"], meshed=True,
                                  mesh=make_host_mesh(dp, mp))
    gc.collect()
    clock.reset()
    ran = []  # the round function train.main built and ran
    t0 = time.perf_counter()
    rc = train.main(argv, round_fn_hook=ran.append)
    wall = time.perf_counter() - t0
    with open(log, newline="") as f:
        losses = [float(r["loss"]) for r in csv.DictReader(f)]
    print(f"[smoke] run {name}: impl={impl} mesh=({dp},{mp}) "
          f"wall_s={wall!r} compile_s={clock.secs!r} "
          f"cache_hits={clock.hits} "
          f"peak_bytes={peak_bytes(jax.local_devices())} "
          f"losses={losses}", flush=True)
    if rc != 0:
        failures.append(f"run {name}: train exit code {rc}")
    if not losses or not all(math.isfinite(v) for v in losses):
        failures.append(f"run {name}: non-finite losses {losses}")
    if want_impl is not None and impl != want_impl:
        failures.append(f"run {name}: impl {impl}, want {want_impl}")
    if opt["--comm-impl"] == "auto" and ran:
        print(f"[smoke] run {name}: comm route coords "
              f"{ran[0].route_coords}", flush=True)
        t0 = time.perf_counter()
        ops = program_ops(ran[0])
        print(f"[smoke] run {name}: round program ops {ops} "
              f"({time.perf_counter() - t0:.1f}s to lower)", flush=True)
        missing = [b for b, o in ops.items() if not o.get("tpu_custom_call")]
        if on_tpu and (missing or not ops):
            failures.append(f"run {name}: no tpu_custom_call in round "
                            f"programs {missing or ops}")
    elif opt["--comm-impl"] == "auto":
        failures.append(f"run {name}: train.main built no round function")
    del ran
    return losses


def compare(failures, name, a, b):
    gap = max((abs(x - y) for x, y in zip(a, b)), default=math.inf)
    ok = len(a) == len(b) and gap <= TOL_LOSS
    print(f"[smoke] losses {name}: max_gap={gap!r} tol={TOL_LOSS!r} "
          f"{'PASS' if ok else 'FAIL'}", flush=True)
    if not ok:
        failures.append(f"losses {name}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, interpret-mode kernels; never "
                         "prints the result line")
    args = ap.parse_args(argv)
    if args.rehearse and args.chips == 4:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")

    # the program itself: without it this fails here, before any device
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.runtime import device_info, enable_compile_cache

    import jax

    # (a) the device, as JAX reports it
    dev = device_info()
    print(f"[smoke] devices: {jax.devices()}", flush=True)
    print(f"[smoke] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    on_tpu = dev["platform"] == "tpu"
    if not on_tpu and not args.rehearse:
        print("[smoke] FAIL: no TPU visible to JAX", flush=True)
        return 1
    if dev["count"] < args.chips:
        print(f"[smoke] FAIL: {args.chips} chips asked, {dev['count']} seen",
              flush=True)
        return 1

    print(f"[smoke] compile cache: {enable_compile_cache()}", flush=True)
    from bench.clock import CompileClock

    clock = CompileClock()
    failures: list = []

    base = ["--arch", "whisper-tiny", "--clients", "8", "--cohort", "6",
            "--sparsity", "2", "--rounds", "4"]
    base += (["--reduced", "--seq-len", "32", "--per-client-batch", "1"]
             if args.rehearse else
             ["--seq-len", "448", "--per-client-batch", "4"])
    with tempfile.TemporaryDirectory() as tmp:
        if args.chips == 4:
            for dp, mp in ((4, 1), (2, 2)):
                tag = f"{dp}x{mp}"
                argv_ = base + ["--max-L", "1"]
                a = train_run(f"cyclic-auto-{tag}",
                              argv_ + ["--comm-impl", "auto"], dp, mp, clock,
                              tmp, failures, on_tpu=on_tpu,
                              want_impl="pallas" if on_tpu else None)
                b = train_run(f"cyclic-dense-{tag}",
                              argv_ + ["--comm-impl", "dense"], dp, mp,
                              clock, tmp, failures, want_impl="dense")
                compare(failures, f"cyclic {tag} auto vs dense", a, b)
        else:
            t0 = time.perf_counter()
            kernel_phase(REHEARSAL_D if args.rehearse else CHIP_D,
                         interpret=not on_tpu, failures=failures,
                         clock=clock)
            print(f"[smoke] kernel phase: {time.perf_counter() - t0:.1f}s",
                  flush=True)
            int8 = ["--wire-precision", "int8", "--robust-agg", "trimmed",
                    "--trim-k", "1", "--max-L", "1"]
            for name, extra in (("cyclic", ["--max-L", "4"]),
                                ("block_rs", ["--uplink", "block_rs",
                                              "--max-L", "1"]),
                                ("int8-trimmed", int8)):
                argv_ = base + extra
                if name == "int8-trimmed":
                    argv_[argv_.index("--sparsity") + 1] = "3"
                a = train_run(f"{name}-auto", argv_ + ["--comm-impl", "auto"],
                              1, 1, clock, tmp, failures, on_tpu=on_tpu,
                              want_impl="pallas" if on_tpu else None)
                b = train_run(f"{name}-dense",
                              argv_ + ["--comm-impl", "dense"], 1, 1, clock,
                              tmp, failures, want_impl="dense")
                compare(failures, f"{name} auto vs dense", a, b)

    if failures:
        print(f"[smoke] FAILED: {failures}", flush=True)
        return 1
    if args.rehearse:
        print("[smoke] rehearsal passed (no chip: no result line)",
              flush=True)
        return 0
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
