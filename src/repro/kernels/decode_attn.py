"""Pallas TPU kernel: single-query GQA decode attention (flash-decode).

The serving hot-spot for decode_32k / long_500k: one new query per sequence
against a KV cache of up to 524k positions.  KV blocks are streamed
HBM->VMEM; an online softmax (running max / denominator in VMEM scratch)
keeps the working set at ``(block_s, head_dim)`` regardless of context
length.  GQA is exploited by loading each KV head once for its whole query
group (``group = n_heads // n_kv_heads`` rows share the tile).

Grid: ``(batch, kv_heads, S // block_s)`` — the S axis iterates fastest so
scratch accumulators carry across KV blocks of one (b, kv-head) pair.
Causal/window masking is applied from the scalar-prefetched ``pos``.

MXU alignment: the q-block is (group, head_dim); head_dim is 64-256 in the
zoo and block_s defaults to 512, so both matmuls hit 128-multiple shapes
for every assigned config (group is padded to 8 lanes by Mosaic if small).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compress import resolve_interpret

NEG_INF = -1e30


def _decode_attn_kernel(
    pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, block_s: int, window: Optional[int], softcap: Optional[float],
    scale: float,
):
    i_s = pl.program_id(2)
    n_s = pl.num_programs(2)
    pos = pos_ref[0]

    @pl.when(i_s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (group, hd)
    k = k_ref[0, :, 0].astype(jnp.float32)  # (block_s, hd)
    v = v_ref[0, :, 0].astype(jnp.float32)  # (block_s, hd)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (group, block_s)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)

    kpos = jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1) + i_s * block_s
    mask = kpos <= pos
    if window is not None:
        mask &= kpos > pos - window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_ref[...]  # (group, 1)
    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)  # (group, block_s)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(i_s == n_s - 1)
    def _finalize():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,  # (b, h, hd)
    k: jax.Array,  # (b, S, kvh, hd)
    v: jax.Array,  # (b, S, kvh, hd)
    pos: jax.Array,  # scalar int32
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_s: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b, h, hd = q.shape
    S, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    assert S % block_s == 0, (S, block_s)
    qg = q.reshape(b, kvh, group, hd)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    out = pl.pallas_call(
        functools.partial(
            _decode_attn_kernel, block_s=block_s, window=window,
            softcap=softcap, scale=1.0 / math.sqrt(hd),
        ),
        grid=(b, kvh, S // block_s),
        in_specs=[
            pl.BlockSpec((1,), lambda ib, ik, i_s: (0,)),  # pos
            pl.BlockSpec(
                (1, 1, group, hd), lambda ib, ik, i_s: (ib, ik, 0, 0)
            ),
            pl.BlockSpec(
                (1, block_s, 1, hd), lambda ib, ik, i_s: (ib, i_s, ik, 0)
            ),
            pl.BlockSpec(
                (1, block_s, 1, hd), lambda ib, ik, i_s: (ib, i_s, ik, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, hd), lambda ib, ik, i_s: (ib, ik, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, group, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),   # running max m
            pltpu.VMEM((group, 1), jnp.float32),   # running denominator l
            pltpu.VMEM((group, hd), jnp.float32),  # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(pos_arr, qg, k, v)
    return out.reshape(b, h, hd)
