"""Plain reference and FLOP count of ``stablelm_3b_share.json``: the
repo's dense decoder block (RMSNorm, rotary attention without q/k/v
biases, SwiGLU) over one layer and the chip's slice of the vocabulary."""

from __future__ import annotations

import jax

from bench import ref_layers as R


def flops_per_sample(cfg, seq: int) -> float:
    """Forward + backward FLOPs of one sample (3x the forward's matrix
    products; causal attention counts its unmasked half; logits over the
    slice's logical vocabulary)."""
    d, f, V = cfg.d_model, cfg.d_ff, cfg.vocab
    layer = 8 * seq * d * d + 2 * seq * seq * d + 6 * seq * d * f
    return 3.0 * (cfg.n_layers * layer + 2 * seq * d * V)


def loss(params, batch, cfg, q=R.exact):
    """Mean token cross-entropy of one client's batch."""
    x = params["embed"][batch["tokens"]]
    for i in range(cfg.n_layers):
        p = jax.tree.map(lambda a: a[i], params["blocks"])
        h = R.rmsnorm(p["ln_attn"], x)
        x = x + R.attention(q, p["attn"], h, h, cfg.n_heads, True,
                            rope_theta=cfg.rope_theta)
        h = R.rmsnorm(p["ln_ff"], x)
        m = p["mlp"]
        x = x + R.mm(q, R.silu(R.mm(q, h, m["w_gate"])) * R.mm(q, h, m["w_up"]),
                     m["w_down"])
    h = R.rmsnorm(params["final_norm"], x)
    return R.xent(q, h, params["lm_head"], batch["labels"], cfg.vocab)
