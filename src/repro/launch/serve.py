"""Batched serving driver: prefill + decode loop over a request batch.

CPU-host demonstration of the inference runtime the decode dry-run shapes
lower for the production mesh.  Requests are prompt token arrays; the loop
prefills each batch (teacher-forced forward writing the KV cache via decode
steps for exactness across families), then decodes greedily.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --reduced \
      --batch 4 --prompt-len 32 --gen-len 16
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-7b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--data-parallel", type=int, default=2)
    ap.add_argument("--model-parallel", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    n_dev = args.data_parallel * args.model_parallel
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={n_dev}"
    )

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import registry
    from repro.dist import model_api, sharding
    from repro.launch.mesh import make_host_mesh
    from repro.launch.runtime import enable_compile_cache

    enable_compile_cache()
    mesh = make_host_mesh(args.data_parallel, args.model_parallel)
    cfg = registry.get_reduced_config(args.arch)
    max_seq = args.prompt_len + args.gen_len

    params = model_api.init(jax.random.key(args.seed), cfg)
    params = jax.device_put(
        params, sharding.params_shardings(params, cfg, mesh)
    )
    cache = model_api.make_cache(cfg, args.batch, max_seq)
    cache_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        sharding.cache_pspecs(cfg, mesh, batch=args.batch),
        is_leaf=lambda x: isinstance(x, P),
    )
    cache = jax.device_put(cache, cache_sh)

    if cfg.family == "encdec":
        from repro.models import encdec

        frames = jax.random.normal(
            jax.random.key(7),
            (args.batch, cfg.n_frames, cfg.d_model), jnp.float32,
        ).astype(cfg.dtype)
        enc = encdec.encode(params, cfg, frames)
        cache = encdec.precompute_cross_kv(params, cfg, enc, cache)

    step = jax.jit(
        lambda p, t, c, pos: model_api.decode(p, cfg, t, c, pos)
    )

    prompts = jax.random.randint(
        jax.random.key(args.seed + 1),
        (args.batch, args.prompt_len), 0, cfg.vocab, jnp.int32,
    )
    t0 = time.time()
    # prefill by stepping the decode path (exact across all families)
    for i in range(args.prompt_len):
        logits, cache = step(
            params, prompts[:, i: i + 1], cache, jnp.asarray(i, jnp.int32)
        )
    generated = []
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    for i in range(args.prompt_len, max_seq):
        generated.append(tok)
        logits, cache = step(params, tok, cache, jnp.asarray(i, jnp.int32))
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    dt = time.time() - t0
    out = jnp.concatenate(generated, axis=1)
    tput = args.batch * (max_seq) / dt
    print(f"[serve] {args.arch}: batch {args.batch}, "
          f"{args.prompt_len}+{len(generated)} tokens/seq, "
          f"{dt:.1f}s ({tput:.1f} tok/s incl. compile)")
    print("[serve] sample continuations:", out[:2].tolist())
    return 0


if __name__ == "__main__":
    sys.exit(main())
