"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def compress_ref(
    x: jax.Array,  # (d,) flat vector (any leading shape flattened by caller)
    slot: jax.Array,  # scalar int32: this client's mask column, >= c if idle
    c: int,
    s: int,
) -> jax.Array:
    """TAMUNA permutation-mask compressor C_i(x): cyclic-band template.

    Coordinate k is owned by columns mod(s*k + t, c), t in [0, s).
    """
    d = x.shape[0]
    k = jnp.arange(d, dtype=jnp.int32)
    owned = (((slot - s * (k % c)) % c) < s) & (slot < c)
    return jnp.where(owned, x, jnp.zeros((), x.dtype))


def _owned_ref(slot, band, m: int, s: int):
    sl = slot[:, None]
    return (sl >= 0) & (sl < m) & (((sl + band[None, :]) % m) < s)


def uplink_masked_sum_ref(
    x: jax.Array,  # (n, d) f32 workspace
    slot: jax.Array,  # (n,) int32
    band: jax.Array,  # (d,) int32
    m: int,
    s: int,
    counts: bool = False,
):
    """Owner-masked client-axis sum with the exact 1/s rebuild; with
    ``counts`` the undivided sum and the per-coordinate owner count."""
    owned = _owned_ref(slot, band, m, s)
    num = jnp.where(owned, x.astype(jnp.float32), 0.0).sum(axis=0)
    if counts:
        return num, owned.astype(jnp.float32).sum(axis=0)
    return num / s


def wire_dequant_ref(codes: jax.Array, scales: jax.Array,
                     chunk: int = 256) -> jax.Array:
    """Int-wire dequant of ONE leaf laid out in contiguous ``chunk``-wide
    scale chunks: ``codes`` (n, d) times each chunk's scale."""
    d = codes.shape[1]
    return codes.astype(jnp.float32) * jnp.repeat(scales, chunk, axis=1)[:, :d]


def uplink_robust_sum_ref(
    x: jax.Array,  # (n, d) workspace
    slot: jax.Array,  # (n,) int32
    band: jax.Array,  # (d,) int32
    m: int,
    s: int,
    kind: str,  # "trimmed" | "median"
    k: int = 0,
):
    """Per-coordinate trimmed mean / median over the owned values, by
    ranks instead of a sort: row i's rank is the number of owned rows
    with a smaller value, ties broken by row index.  Returns
    ``(x_bar, cnt)``: 0 where no row owns the coordinate."""
    owned = _owned_ref(slot, band, m, s)
    x = x.astype(jnp.float32)
    n = x.shape[0]
    cnt = owned.astype(jnp.int32).sum(axis=0)
    ranks = []
    for i in range(n):
        below = (x < x[i][None, :]) | (
            (x == x[i][None, :]) & (jnp.arange(n)[:, None] < i))
        ranks.append((owned & below).astype(jnp.int32).sum(axis=0))
    rank = jnp.stack(ranks)

    def at(r):  # the r-th smallest owned value per coordinate
        return jnp.where(owned & (rank == r[None, :]), x, 0.0).sum(axis=0)

    if kind == "median":
        bar = 0.5 * (at(jnp.maximum(cnt - 1, 0) // 2) + at(cnt // 2))
    else:
        ke = jnp.clip(jnp.minimum(k, jnp.maximum(cnt - 1, 0) // 2), 0)
        use = owned & (rank >= ke[None, :]) & (rank < (cnt - ke)[None, :])
        bar = (jnp.where(use, x, 0.0).sum(axis=0)
               / jnp.maximum(cnt - 2 * ke, 1).astype(jnp.float32))
    return jnp.where(cnt > 0, bar, 0.0), cnt.astype(jnp.float32)


def uplink_h_update_ref(
    x: jax.Array,
    h: jax.Array,
    x_bar: jax.Array,
    slot: jax.Array,
    band: jax.Array,
    m: int,
    s: int,
    scale: float,
    down: Optional[jax.Array] = None,  # (n,) DownCom rows; None = all
    covered: Optional[jax.Array] = None,  # (d,) coords with an owner
):
    """Control-variate update on owned coordinates + DownCom (``down``
    rows get ``x_bar``; all rows when None).  ``covered`` leaves the
    coordinates outside it untouched in both h and x."""
    owned = _owned_ref(slot, band, m, s)
    rows = (jnp.ones(x.shape[:1], bool) if down is None
            else down.astype(bool))
    if covered is not None:
        owned = owned & covered[None, :]
        rows = rows[:, None] & covered[None, :]
    else:
        rows = rows[:, None]
    h_new = h + scale * jnp.where(owned, x_bar[None, :] - x, 0.0)
    x_new = jnp.where(rows, jnp.broadcast_to(x_bar[None, :], x.shape), x)
    return h_new, x_new


def fused_local_step_ref(
    x: jax.Array, g: jax.Array, h: jax.Array, gamma: float
) -> jax.Array:
    """TAMUNA local step x <- x - gamma*g + gamma*h (f32 accumulate)."""
    xf = x.astype(jnp.float32)
    out = xf - gamma * g.astype(jnp.float32) + gamma * h.astype(jnp.float32)
    return out.astype(x.dtype)


def decode_attention_ref(
    q: jax.Array,  # (b, h, hd) single-position queries
    k: jax.Array,  # (b, S, kvh, hd) cache keys
    v: jax.Array,  # (b, S, kvh, hd) cache values
    pos: jax.Array,  # scalar int32: index of the newest token (inclusive)
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Single-token GQA decode attention over a KV cache (f32 softmax)."""
    b, h, hd = q.shape
    S, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    qg = q.reshape(b, kvh, group, hd).astype(jnp.float32)
    logits = jnp.einsum(
        "bkgd,bskd->bkgs", qg, k.astype(jnp.float32)
    ) / math.sqrt(hd)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    kpos = jnp.arange(S)
    mask = kpos <= pos
    if window is not None:
        mask &= kpos > pos - window
    logits = jnp.where(mask[None, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(b, h, hd).astype(q.dtype)
