import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture x input-shape x mesh)
combination lowers + compiles on the production mesh, and extract the
roofline terms from the compiled artifact.

The two lines above MUST stay first: jax locks the device count on first
init, and the dry-run needs 512 placeholder host devices for the 2x16x16
mesh.  (Smoke tests and benches run in separate processes and see 1 device.)

Usage:
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--uplink block_rs]
  python -m repro.launch.dryrun --all --both-meshes

Artifacts: benchmarks/artifacts/dryrun/<mesh>/<arch>/<shape>/<step>.json
holding memory_analysis, cost_analysis, per-collective byte counts, and the
roofline terms (see benchmarks/roofline.py and EXPERIMENTS.md §Roofline).
"""

import argparse
import dataclasses
import json
import re
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import numpy as np

from repro.configs import registry
from repro.dist import comm_ws, robust as robust_lib, wire as wire_lib
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_production_mesh

# hardware constants (TPU v5e target; see the assignment)
PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
LINK_BW = 50e9  # bytes/s per ICI link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_LINE_RE = re.compile(
    r"=\s*(?P<result>.*?)\s"
    r"(?P<kind>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<async>-start|-done)?\("
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device bytes of collective results in the post-SPMD module.

    Handles tuple-result collectives (XLA combines many leaves into one op).
    Async pairs are counted once (-start counted, -done skipped).
    """
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_LINE_RE.search(line)
        if not m or m.group("async") == "-done":
            continue
        kind = m.group("kind")
        size = 0.0
        for dt, dims in _SHAPE_RE.findall(m.group("result")):
            if dt not in _DTYPE_BYTES:
                continue
            b = _DTYPE_BYTES[dt]
            if dims:
                for d in dims.split(","):
                    b *= int(d)
            size += b
        out[kind] = out.get(kind, 0.0) + size
    out["total"] = sum(out.values())
    return out


def model_flops(arch: str, shape_name: str) -> float:
    """MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE), D = tokens."""
    from repro.dist import model_api

    cfg = steps_lib.dryrun_model_cfg(arch, shape_name)
    sh = registry.SHAPES[shape_name]
    params_struct = jax.eval_shape(
        lambda: model_api.init(jax.random.key(0), cfg)
    )
    if cfg.family == "moe":
        n_params = cfg.active_param_count(params_struct)
    else:
        n_params = sum(x.size for x in jax.tree.leaves(params_struct))
    tokens = sh.global_batch * (sh.seq_len if sh.kind != "decode" else 1)
    mult = 6.0 if sh.kind == "train" else 2.0  # fwd+bwd vs fwd-only
    return mult * n_params * tokens


def wire_summary(arch: str, shape_name: str, tcfg) -> dict:
    """The comm step's resolved wire format: per-leaf kinds (builder-time
    size-adaptive policy) and the per-client wire bytes one round costs —
    the artifact records what actually travels, not just the policy."""
    from repro.core import masks
    from repro.dist import model_api

    cfg = steps_lib.dryrun_model_cfg(arch, shape_name)
    params_struct = jax.eval_shape(
        lambda: model_api.init(jax.random.key(0), cfg)
    )
    dims = [int(np.prod(a.shape)) for a in jax.tree.leaves(params_struct)]
    kinds = [wire_lib.resolve_kind(D, tcfg.wire_precision) for D in dims]
    nnz = masks.block_column_nnz if tcfg.uplink == "block_rs" \
        else masks.column_nnz
    counts: Dict[str, int] = {}
    for k in kinds:
        counts[k] = counts.get(k, 0) + 1
    return {
        "policy": tcfg.wire_precision,
        "leaf_kind_counts": counts,
        "leaf_kinds": kinds,
        "up_bytes_per_round": sum(
            wire_lib.leaf_up_bytes(nnz(D, tcfg.c, tcfg.s), D, 1, k)
            for D, k in zip(dims, kinds)
        ),
        "down_bytes_per_round": sum(
            wire_lib.leaf_down_bytes(D, k if tcfg.wire_down else "f32")
            for D, k in zip(dims, kinds)
        ),
    }


def run_one(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    uplink: str = "masked_psum",
    comm_impl: str = "auto",
    wire_precision: str = "f32",
    robust_agg: str = "mean",
    trim_k: int = 0,
    out_dir: Optional[str] = None,
    verbose: bool = True,
) -> Dict[str, dict]:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    tcfg = steps_lib.default_tamuna_cfg(mesh, uplink=uplink,
                                        comm_impl=comm_impl,
                                        wire_precision=wire_precision,
                                        robust_agg=robust_agg,
                                        trim_k=trim_k)
    built = steps_lib.build(arch, shape_name, mesh, **(
        {"tcfg": tcfg} if registry.SHAPES[shape_name].kind == "train" else {}
    ))

    results = {}
    for step_name, b in built.items():
        t0 = time.time()
        with mesh:
            jitted = jax.jit(
                b.fn,
                in_shardings=b.in_shardings,
                out_shardings=b.out_shardings,
            )
            lowered = jitted.lower(*b.in_specs)
            compiled = lowered.compile()
        t1 = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        # older jax returns [dict] (one entry per program), newer a dict
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        hlo = compiled.as_text()
        # XLA's cost_analysis counts while bodies ONCE (useless for scanned
        # layer stacks); hlo_analysis re-derives flops / bytes / collective
        # bytes with while-loop trip counts applied (see hlo_analysis.py).
        from repro.launch import hlo_analysis

        ha = hlo_analysis.analyze(hlo)
        coll = dict(ha.collective_bytes)
        coll["total"] = ha.collective_total

        flops_total = float(ha.flops)
        bytes_total = float(ha.bytes_accessed)
        # post-SPMD HLO shapes are per-partition, so all terms are per-chip.
        compute_s = flops_total / PEAK_FLOPS
        memory_s = bytes_total / HBM_BW
        coll_s = coll["total"] / LINK_BW
        mflops = model_flops(arch, shape_name)

        rec = {
            "arch": arch,
            "shape": shape_name,
            "step": step_name,
            "mesh": mesh_name,
            "chips": n_chips,
            "uplink": uplink if step_name in ("comm", "round") else None,
            # the impl that actually executes: make_comm_step runs meshed
            # (clients are device-sharded) WITH the mesh handle, so
            # "pallas" resolves to the shard-resident engine (§10), not
            # the pre-shard_map ws fallback — see comm_ws.effective_impl
            "comm_impl": (
                comm_ws.effective_impl(tcfg.comm_impl, meshed=True,
                                       mesh=mesh)
                if step_name in ("comm", "round") else None
            ),
            # resolved per-leaf wire precision (§13): what each leaf
            # actually ships, not just the policy name
            "wire": (
                wire_summary(arch, shape_name, tcfg)
                if step_name in ("comm", "round") else None
            ),
            # coordinates of a client row per comm route (leaf-native
            # kernels, packed workspace, per-leaf jnp; DESIGN.md §10), as
            # the comm step lowered here dispatches them
            "routes": getattr(b.fn, "route_coords", None),
            # robust combiner over the s owner values (DESIGN.md §15):
            # mean / trimmed-k / median; mean (and trimmed k=0) lowers
            # the bitwise legacy aggregation
            "robust": (
                {"agg": tcfg.robust_agg, "trim_k": tcfg.trim_k}
                if step_name in ("comm", "round") else None
            ),
            "compile_s": round(t1 - t0, 2),
            "memory_analysis": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_bytes": getattr(
                    mem, "generated_code_size_in_bytes", None
                ),
            },
            "cost_analysis": {
                "flops": flops_total,
                "bytes_accessed": bytes_total,
                "xla_raw_flops": float(cost.get("flops", 0.0)),
                "xla_raw_bytes": float(cost.get("bytes accessed", 0.0)),
            },
            "collective_bytes": coll,
            "roofline": {
                "compute_s": compute_s,
                "memory_s": memory_s,
                "collective_s": coll_s,
                "dominant": max(
                    [("compute", compute_s), ("memory", memory_s),
                     ("collective", coll_s)],
                    key=lambda kv: kv[1],
                )[0],
                "model_flops_global": mflops,
                "model_flops_per_chip": mflops / n_chips,
                "useful_flops_ratio": (
                    (mflops / n_chips) / flops_total
                    if flops_total else None
                ),
            },
        }
        results[step_name] = rec
        if verbose:
            r = rec["roofline"]
            print(
                f"[dryrun] {arch} {shape_name} {step_name} {mesh_name}: "
                f"compile {rec['compile_s']}s  "
                f"compute {r['compute_s']:.3e}s  mem {r['memory_s']:.3e}s  "
                f"coll {r['collective_s']:.3e}s  -> {r['dominant']}",
                flush=True,
            )
        if out_dir:
            d = os.path.join(out_dir, mesh_name, arch, shape_name)
            os.makedirs(d, exist_ok=True)
            with open(os.path.join(d, f"{step_name}.json"), "w") as f:
                json.dump(rec, f, indent=1)
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.list_archs())
    ap.add_argument("--shape", choices=list(registry.SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--uplink", default="masked_psum",
                    choices=["masked_psum", "block_rs"])
    ap.add_argument("--comm-impl", default="auto",
                    choices=list(comm_ws.COMM_IMPLS),
                    help="comm-step aggregation path (DESIGN.md §9); auto "
                         "= fused workspace off-TPU, Pallas kernels on TPU")
    ap.add_argument("--wire-precision", default="f32",
                    choices=list(wire_lib.WIRE_POLICIES),
                    help="UpCom payload width (DESIGN.md §13); the "
                         "artifact records the resolved per-leaf kinds")
    ap.add_argument("--robust-agg", default="mean",
                    choices=list(robust_lib.ROBUST_AGGS),
                    help="per-coordinate combiner over the s owner "
                         "values (DESIGN.md §15); the artifact records "
                         "the lowered aggregation")
    ap.add_argument("--trim-k", type=int, default=0,
                    help="values trimmed per side for --robust-agg "
                         "trimmed (needs 2k < s)")
    ap.add_argument("--out-dir", default="benchmarks/artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    pairs = []
    if args.all:
        for a in registry.list_archs():
            for s in registry.SHAPES:
                if registry.supported(a, s):
                    pairs.append((a, s))
                else:
                    print(f"[dryrun] SKIP {a} {s} (documented in DESIGN.md)")
    else:
        if not (args.arch and args.shape):
            ap.error("--arch/--shape or --all required")
        pairs = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = []
    for mp in meshes:
        for a, s in pairs:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            if args.skip_existing and args.out_dir:
                kind = registry.SHAPES[s].kind
                probe = {"train": "local", "prefill": "prefill",
                         "decode": "decode"}[kind]
                p = os.path.join(args.out_dir, mesh_name, a, s,
                                 f"{probe}.json")
                if os.path.exists(p):
                    print(f"[dryrun] skip existing {a} {s} {mesh_name}")
                    continue
            try:
                run_one(a, s, mp, uplink=args.uplink,
                        comm_impl=args.comm_impl,
                        wire_precision=args.wire_precision,
                        robust_agg=args.robust_agg, trim_k=args.trim_k,
                        out_dir=args.out_dir)
            except Exception:
                traceback.print_exc()
                failures.append((a, s, mesh_name))
    if failures:
        print(f"[dryrun] FAILURES: {failures}")
        return 1
    print("[dryrun] all combinations lowered + compiled OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
