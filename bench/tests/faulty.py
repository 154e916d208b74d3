"""Run one cell at its rehearsal size on the CPU with the timed path
broken underneath, and print the result line.

    python3 -m bench.tests.faulty <fault> <cell>

Faults, each planted in the program before the harness builds its round
engine:

* ``none``: the sound program (the engine on the TPU's shard-resident
  kernels, interpreted);
* ``unchanged``: local and comm steps return their state unchanged;
* ``half_batch``: each client's loss and gradient over the first half of
  its batch, the mean taken over that half;
* ``token``: every target token of each client's first sequence altered
  where the local step reads it.
"""

from __future__ import annotations

import json
import os
import sys
import time


def plant(fault: str) -> None:
    from repro.dist import comm_ws, rounds, tamuna_dp

    # the impl the chip resolves: the shard-resident Pallas engine
    real_resolve = comm_ws.resolve_impl
    comm_ws.resolve_impl = lambda impl: (
        "pallas" if (impl or "auto") == "auto" else real_resolve(impl))
    if fault == "none":
        return
    real_local = tamuna_dp.make_local_step
    real_comm = tamuna_dp.make_comm_step

    def local(cfg, tcfg):
        fn = real_local(cfg, tcfg)

        def broken(state, **batch):
            if fault == "half_batch":
                batch = {k: v[:, : v.shape[1] // 2] for k, v in batch.items()}
            if fault == "token":
                lab = batch["labels"]
                batch = {**batch, "labels": lab.at[:, 0].set(
                    (lab[:, 0] + 1) % 7)}
            new, m = fn(state, **batch)
            return (state if fault == "unchanged" else new), m

        return broken

    def comm(*a, **kw):
        fn = real_comm(*a, **kw)
        if fault != "unchanged":
            return fn
        return lambda state, *args, **kws: state

    tamuna_dp.make_local_step = local
    tamuna_dp.make_comm_step = comm
    rounds.tamuna_dp = tamuna_dp


def main(fault: str, cell: str) -> int:
    t0 = time.perf_counter()
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from bench import harness

    chips = next(w["chips"] for w in harness.benchmark()["workloads"]
                 if w["name"] == cell)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    plant(fault)
    out = harness.run_cell(cell, 12345, 1.0, False, t_start=t0,
                           rehearse=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
