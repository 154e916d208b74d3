"""Plain building blocks of the reference models: straightforward
``jax.numpy`` in float32, every matrix product at ``HIGHEST`` precision.

Every matrix product takes its operands through ``q``: ``exact`` (the
reference) or ``fp8`` (the control: operands rounded to float8 e4m3, the
precision below the bfloat16 the configurations compute in).  The
control's rounding is straight-through for the backward pass, so its
gradients see the rounded forward values and exact cotangents."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def exact(a):
    return a


@jax.custom_vjp
def fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


def _fp8_fwd(a):
    return fp8(a), None


def _fp8_bwd(_, g):
    return (g,)


fp8.defvjp(_fp8_fwd, _fp8_bwd)

QUANT = {"exact": exact, "fp8": fp8}


def mm(q, a, b):
    """``a @ b`` over the last axis of ``a``."""
    return jnp.matmul(q(a), q(b), precision=HIGHEST)


def layernorm(p, x, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rmsnorm(p, x, eps=1e-6):
    """The repo's RMSNorm: the learned scale multiplies as (1 + scale)."""
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (
        1.0 + p["scale"])


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def sinusoid(t, d):
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2.0 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def rope(x, theta):
    """Rotary embedding over the whole head (halves rotated together):
    ``x`` is ``(b, t, heads, hd)``."""
    t, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _proj(q_, x, p, w):
    """``x @ p[w]``, plus the projection's bias where the params have one
    (``wq`` -> ``bq``)."""
    y = mm(q_, x, p[w])
    return y + p["b" + w[1:]] if "b" + w[1:] in p else y


def attention(q_, p, xq, xkv, heads, causal, rope_theta=None):
    """Multi-head attention with optional q/k/v biases and no output
    bias."""
    b, tq, d = xq.shape
    tk = xkv.shape[1]
    hd = d // heads
    q = _proj(q_, xq, p, "wq").reshape(b, tq, heads, hd)
    k = _proj(q_, xkv, p, "wk").reshape(b, tk, heads, hd)
    v = _proj(q_, xkv, p, "wv").reshape(b, tk, heads, hd)
    if rope_theta is not None:
        q, k = rope(q, rope_theta), rope(k, rope_theta)
    s = jnp.einsum("bqhd,bkhd->bhqk", q_(q), q_(k),
                   precision=HIGHEST) / math.sqrt(hd)
    if causal:
        keep = jnp.arange(tk)[None, :] <= jnp.arange(tq)[:, None]
        s = jnp.where(keep[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", q_(w), q_(v), precision=HIGHEST)
    return mm(q_, o.reshape(b, tq, d), p["wo"])


def xent(q_, h, w_out, labels, vocab):
    """Mean next-token cross-entropy over the ``vocab`` logical ids
    (``w_out`` may carry padded columns, which no id reaches)."""
    logits = mm(q_, h, w_out[:, :vocab])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()
