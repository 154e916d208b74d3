#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell,
in one process: per seed, the gaps of the program's first rounds to the
reference, and of the control (the reference with float8 matrix
operands) and of faults planted in the reference, to the reference.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3] [--faults half_batch token]

Prints one JSON line per seed and reading, then the largest program gap
and the smallest control gap of each number.  The benchmark's own runs
do not run this; see PERF.md for the limits it gave.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[],
                    help="faults planted in the reference, read on the "
                         "control seeds: half_batch, token")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    if args.rehearse:
        from bench.harness import benchmark

        chips = next(w["chips"] for w in benchmark()["workloads"]
                     if w["name"] == args.workload)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={chips}")
    import jax

    from bench import correct
    from bench.harness import Cell

    cell = Cell(args.workload, args.rehearse)
    prog_gaps, ctl_gaps = [], []
    for seed in args.seeds:
        with jax.set_mesh(cell.mesh):
            state, data, prog = cell.first_rounds(seed)
        for a in jax.tree.leaves((state, data)):
            a.delete()
        ref = cell.reference(seed)
        g = correct.gaps(prog, ref)
        prog_gaps.append(g)
        print(json.dumps({"seed": seed, "side": "program", **g,
                          "loss": prog["loss"], "ref_loss": ref["loss"]}),
              flush=True)
        if seed in args.control_seeds:
            ctl = cell.reference(seed, "fp8")
            gc = correct.gaps(ctl, ref)
            ctl_gaps.append(gc)
            print(json.dumps({"seed": seed, "side": "control", **gc,
                              "loss": ctl["loss"]}), flush=True)
            for fault in args.faults:
                gf = correct.gaps(cell.reference(seed, fault=fault), ref)
                print(json.dumps({"seed": seed, "side": fault, **gf}),
                      flush=True)
    summary = {k: {"program_max": max(g[k] for g in prog_gaps),
                   "control_min": (min(g[k] for g in ctl_gaps)
                                   if ctl_gaps else None)}
               for k in correct.LIMIT_KEYS}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
