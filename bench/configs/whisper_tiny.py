"""Plain reference and FLOP count of ``whisper_tiny.json``: Whisper's
encoder-decoder as the repo defines it (the departures from the paper
are the file's ``assumed``), over the repo's parameter layout."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import ref_layers as R


def flops_per_sample(cfg, seq: int) -> float:
    """Forward + backward FLOPs of one sample (3x the forward's matrix
    products; causal self-attention counts its unmasked half, the encoder
    and cross attention are full; logits over the logical vocabulary)."""
    d, f, F, V = cfg.d_model, cfg.d_ff, cfg.n_frames, cfg.vocab
    enc = cfg.n_encoder_layers * (8 * F * d * d + 4 * F * d * f
                                  + 4 * F * F * d)
    dec = cfg.n_layers * (8 * seq * d * d + 2 * seq * seq * d
                          + 4 * seq * d * d + 4 * F * d * d
                          + 4 * seq * F * d + 4 * seq * d * f)
    head = 2 * seq * d * V
    return 3.0 * (enc + dec + head)


def _block(q, p, x, heads, causal, enc=None):
    h = R.layernorm(p["ln_attn"], x)
    x = x + R.attention(q, p["attn"], h, h, heads, causal)
    if enc is not None:
        x = x + R.attention(q, p["xattn"], R.layernorm(p["ln_x"], x), enc,
                            heads, causal=False)
    h = R.layernorm(p["ln_ff"], x)
    return x + R.mm(q, R.gelu_tanh(R.mm(q, h, p["mlp"]["w_up"])),
                    p["mlp"]["w_down"])


def loss(params, batch, cfg, q=R.exact):
    """Mean token cross-entropy of one client's batch."""
    heads = cfg.n_heads
    layer = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    fr = batch["frames"].astype(jnp.float32)
    x = fr + R.sinusoid(fr.shape[1], cfg.d_model)[None]
    for i in range(cfg.n_encoder_layers):
        x = _block(q, layer(params["enc_blocks"], i), x, heads, False)
    enc = R.layernorm(params["enc_norm"], x)
    tok = batch["tokens"]
    y = params["embed"][tok] + R.sinusoid(tok.shape[1], cfg.d_model)[None]
    for i in range(cfg.n_layers):
        y = _block(q, layer(params["dec_blocks"], i), y, heads, True, enc)
    h = R.layernorm(params["final_norm"], y)
    return R.xent(q, h, params["embed"].T, batch["labels"], cfg.vocab)
