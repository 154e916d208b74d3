"""Jit'd public wrappers around the Pallas kernels.

``interpret`` mode is selected automatically (``compress.resolve_interpret``):
off-TPU the kernels execute their bodies in the Pallas interpreter
(bit-accurate validation); on a TPU backend they compile via Mosaic.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import compress as _compress
from repro.kernels import decode_attn as _decode_attn
from repro.kernels import local_step as _local_step
from repro.kernels import uplink as _uplink


@partial(jax.jit, static_argnames=("c", "s", "block"))
def compress(x, slot, c: int, s: int, block: int = 4096):
    """C_i(x): (d,) with slot (1,), or client-stacked (n, d) with slot
    (n,) — the 2-D form runs a grid over clients."""
    return _compress.compress(
        x, slot, c, s, block=block
    )


@partial(jax.jit, static_argnames=("m", "s", "block"))
def uplink_masked_sum(x, slot, band, m: int, s: int, block: int = 4096):
    """Mask-free UpCom over the (n, d) comm workspace, 1/s rebuild fused."""
    return _uplink.masked_sum(
        x, slot, band, m, s, block=block
    )


@partial(jax.jit, static_argnames=("m", "s", "scale", "block"))
def uplink_h_update(x, h, x_bar, slot, band, m: int, s: int, scale: float,
                    down=None, block: int = 4096):
    """Fused control-variate update + DownCom, one pass.  ``down`` selects
    the rows that receive ``x_bar`` (all rows when None)."""
    return _uplink.h_update(
        x, h, x_bar, slot, band, m, s, scale, down=down, block=block,
    )


@partial(jax.jit, static_argnames=("gamma", "block"))
def fused_local_step(x, g, h, gamma: float, block: int = 65536):
    """x <- x - gamma*(g - h), any shape, storage-dtype preserving."""
    return _local_step.fused_local_step(
        x, g, h, gamma, block=block
    )


@partial(jax.jit, static_argnames=("window", "softcap", "block_s"))
def decode_attention(
    q, k, v, pos,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_s: int = 512,
):
    """Flash-decode GQA attention: q (b,h,hd) vs cache k/v (b,S,kvh,hd)."""
    return _decode_attn.decode_attention(
        q, k, v, pos, window=window, softcap=softcap, block_s=block_s,
    )


def make_attend_fn(
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_s: int = 512,
):
    """Adapter plugging the Pallas decode kernel into the model decode path
    (``transformer.decode_step(..., attend_fn=...)`` /
    ``layers.attention_decode``).  ``window`` must be static here; archs
    with per-layer dynamic windows use the jnp reference instead.
    """

    def attend(q, cache_k, cache_v, pos, dyn_window=None):
        del dyn_window  # static-window kernel variant
        b, t, h, hd = q.shape
        assert t == 1, "decode kernel is single-query"
        S = cache_k.shape[1]
        bs = block_s if S % block_s == 0 else S
        out = decode_attention(
            q[:, 0], cache_k.astype(q.dtype), cache_v.astype(q.dtype),
            pos, window=window, softcap=softcap, block_s=bs,
        )
        return out[:, None]

    return attend
