#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights and data from the seed on the device, the bucket
programs from JAX's compile cache in ``<checkout>/.jax_cache``, one warm
round of each) counts into ``setup_s``; then the TAMUNA round engine
trains for ``--seconds`` (whole blocks of the cell's round lengths; a
traced run the same, under the profiler) and its first rounds are
checked against the plain reference.  The last stdout line is the result; the
numbers compared, each beside its limit, end both it and stderr.

A machine without a TPU, or with fewer chips than the cell needs, exits
2 and prints no result.  ``--rehearse`` runs the same path on the CPU at
the configurations' rehearsal sizes: control flow only, no result line.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, rehearsal sizes, no result line")
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # the cache lives in the checkout at a fixed path, whatever the
    # environment says: its path is part of every entry's key
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    # unbounded: an eviction bound below one round program's entry would
    # keep nothing, and every run would compile again
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    if args.rehearse:
        from bench.harness import benchmark

        chips = next(w["chips"] for w in benchmark()["workloads"]
                     if w["name"] == args.workload)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={chips}")

    from repro.launch.runtime import enable_compile_cache

    enable_compile_cache()
    from bench import harness

    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START,
                               rehearse=args.rehearse)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    if args.rehearse:
        harness.log("rehearsal: " + json.dumps(out))
        return 0
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
