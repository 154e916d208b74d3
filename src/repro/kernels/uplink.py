"""Pallas TPU kernels: the mask-free fused TAMUNA comm step.

Both kernels run over the flat comm workspace (``dist/comm_ws.py``): the
client-stacked state packed to an ``(n, d)`` f32 buffer, ownership encoded
by a static per-coordinate ``band`` table and a per-client ``slot`` vector,
evaluated per VMEM tile via ``compress.owned_from_band`` — no ``(n, d)``
or ``(d, c)`` mask is ever materialized in HBM.

  masked_sum  UpCom: per-tile ownership, masked client-axis sum, and the
              exact ``1/s`` rebuild fused into one pass — 1 read of x and
              a ``d``-sized write, vs the dense reference's mask write +
              mask read + masked-product materialization.  The payload
              lanes may be the narrow float wire dtype (bf16/f16,
              ``dist/wire.py``); accumulation is always f32.
              Int-wire codes are dequantized ahead of the kernel
              (``compress.wire_dequant``).
  h_update    the round's state update: reads x, h and the server model
              x_bar once and writes BOTH h_new (control variates, owned
              coordinates only) and the DownCom'd x_new in the same pass —
              2 reads + 2 writes, the HBM floor for this update.  The
              per-client ``down`` vector selects which rows receive the
              ``x_bar`` broadcast: under elastic partial participation
              (DESIGN.md §11) only the NEXT round's cohort downloads, so
              idle clients' rows pass through bit-exactly.

Grid: 1-D over coordinate blocks; tiles are ``(n, blk)``.  ``block`` is
only a cap: ``compress.fit_block`` narrows it from n and the operand
dtypes so the double-buffered tiles plus the in-tile f32 temporaries
stay inside the scoped VMEM the kernels ask for (at n=512 ``h_update``
runs 1024-wide blocks; the old fixed 4096 ran Mosaic out of VMEM).  A
ragged last block is a partial grid step, never a padded copy of the
``(n, d)`` operands.
``interpret=None`` auto-detects the backend (Mosaic on TPU, interpreter
elsewhere); CPU CI exercises exactly these bodies in interpret mode
(tests/test_kernels.py), while the CPU production path uses the
equivalent fused-jnp workspace math.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import spans
from repro.kernels.compress import (
    TILED_PARAMS,
    fit_block,
    owned_from_band,
    resolve_interpret,
)

__all__ = ["masked_sum", "robust_sum", "h_update"]


def _masked_sum_kernel(slot_ref, band_ref, x_ref, o_ref, *, m: int, s: int):
    owned = owned_from_band(
        slot_ref[...][:, None], band_ref[...][None, :], m, s
    )
    # workspace lanes may be the narrow float wire dtype (bf16/f16);
    # accumulation is always f32 (a no-op cast on the f32 path)
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.where(owned, x, 0.0).sum(axis=0) / s


def _masked_sum_counts_kernel(
    slot_ref, band_ref, x_ref, num_ref, cnt_ref, *, m: int, s: int
):
    # survivor-aware variant: raw masked sum + per-coordinate arrived
    # owner count (no /s — the caller divides after any psum so the
    # count stays exact across shards).  Dropped clients arrive here
    # with slot = -1, which owns nothing.
    owned = owned_from_band(
        slot_ref[...][:, None], band_ref[...][None, :], m, s
    )
    x = x_ref[...].astype(jnp.float32)
    num_ref[...] = jnp.where(owned, x, 0.0).sum(axis=0)
    cnt_ref[...] = owned.astype(jnp.float32).sum(axis=0)


def _robust_sum_kernel(
    slot_ref, band_ref, x_ref, bar_ref, cnt_ref,
    *, m: int, s: int, kind: str, k: int,
):
    # Byzantine-robust UpCom (DESIGN.md §15): per-coordinate trimmed
    # mean / median over the arrived owner values, fused in-tile.  The
    # owner stack is sorted by s passes of masked-min extraction
    # (argmin-free: ties break by first row, one occurrence removed per
    # pass) — s is small and static, so the per-tile cost is s
    # client-axis reductions instead of a full sort network, and the
    # loop unrolls into pure VPU selects.  Values past the arrived
    # count never enter the combine.
    owned = owned_from_band(
        slot_ref[...][:, None], band_ref[...][None, :], m, s
    )
    x = x_ref[...].astype(jnp.float32)
    n = x.shape[0]
    cnt = owned.astype(jnp.int32).sum(axis=0)
    big = jnp.asarray(jnp.inf, jnp.float32)
    # row index per element: the first hit row is the min row index
    # among the hits (a client-axis min; Mosaic has no cumsum lowering)
    rid = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    active = owned
    order = []  # order[t] = t-th smallest arrived owner value (+inf past cnt)
    for _ in range(s):
        v = jnp.where(active, x, big)
        mn = v.min(axis=0)
        hit = (v == mn[None, :]) & active
        first_row = jnp.where(hit, rid, n).min(axis=0)
        active = active & (rid != first_row[None, :])
        order.append(mn)
    zero = jnp.zeros((), jnp.float32)
    if kind == "median":
        # (cnt - 1) // 2 and cnt // 2 as shifts: cnt >= 0 here
        loi = jnp.maximum(cnt - 1, 0) >> 1
        hii = cnt >> 1
        lo = hi = zero
        for t, mn in enumerate(order):
            lo = jnp.where(loi == t, mn, lo)
            hi = jnp.where(hii == t, mn, hi)
        bar = 0.5 * (lo + hi)  # lo == hi at odd counts: exact
    else:  # trimmed
        k_eff = jnp.clip(jnp.minimum(k, jnp.maximum(cnt - 1, 0) >> 1), 0)
        num = zero
        for t, mn in enumerate(order):
            use = (t >= k_eff) & (t < cnt - k_eff)
            num = num + jnp.where(use, mn, zero)
        bar = num / jnp.maximum(cnt - 2 * k_eff, 1).astype(jnp.float32)
    bar_ref[...] = jnp.where(cnt > 0, bar, zero)
    cnt_ref[...] = cnt.astype(jnp.float32)


def _h_update_kernel(
    slot_ref, down_ref, band_ref, xbar_ref, x_ref, h_ref, h_out, x_out,
    *, m: int, s: int, scale: float,
):
    owned = owned_from_band(
        slot_ref[...][:, None], band_ref[...][None, :], m, s
    )
    x = x_ref[...]
    x_bar = xbar_ref[...][None, :]
    h_out[...] = h_ref[...] + scale * jnp.where(owned, x_bar - x, 0.0)
    down = down_ref[...][:, None] != 0
    x_out[...] = jnp.where(down, jnp.broadcast_to(x_bar, x.shape), x)


def _h_update_covered_kernel(
    slot_ref, down_ref, band_ref, cov_ref, xbar_ref, x_ref, h_ref,
    h_out, x_out, *, m: int, s: int, scale: float,
):
    # survivor-aware variant: uncovered coordinates (no arrived owner)
    # have an x_bar rebuilt from nothing — gate both the control-variate
    # update and the DownCom so those coordinates pass through
    # bit-exactly (PR 5's idle-client semantics, per-coordinate).
    owned = owned_from_band(
        slot_ref[...][:, None], band_ref[...][None, :], m, s
    )
    cov = cov_ref[...][None, :] != 0
    x = x_ref[...]
    x_bar = xbar_ref[...][None, :]
    h_out[...] = h_ref[...] + scale * jnp.where(
        owned & cov, x_bar - x, 0.0
    )
    down = (down_ref[...][:, None] != 0) & cov
    x_out[...] = jnp.where(down, jnp.broadcast_to(x_bar, x.shape), x)


def _col_call(kernel, n: int, d: int, blk: int, in_specs, n_vec_out: int,
              n_mat_out: int, interpret, name: str):
    """``pallas_call`` over a 1-D grid of ``blk``-wide coordinate blocks
    (the last one partial when ``blk`` does not divide ``d``) with
    ``n_vec_out`` f32 ``(d,)`` then ``n_mat_out`` f32 ``(n, d)`` outputs.
    ``in_specs`` entries are ``"row"`` ((n,) whole), ``"vec"`` ((blk,)
    slice) or ``"mat"`` ((n, blk) tile).  The call runs under the
    ``tamuna.uplink`` scope and carries the kernel's ``name``."""
    spec = {
        "row": pl.BlockSpec((n,), lambda i: (0,)),
        "vec": pl.BlockSpec((blk,), lambda i: (i,)),
        "mat": pl.BlockSpec((n, blk), lambda i: (0, i)),
    }
    outs = ["vec"] * n_vec_out + ["mat"] * n_mat_out
    shapes = [(d,)] * n_vec_out + [(n, d)] * n_mat_out
    out_specs = tuple(spec[o] for o in outs)
    out_shape = tuple(jax.ShapeDtypeStruct(sh, jnp.float32) for sh in shapes)
    if len(outs) == 1:
        out_specs, out_shape = out_specs[0], out_shape[0]
    call = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(d, blk),),
        in_specs=[spec[i] for i in in_specs],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=TILED_PARAMS,
        interpret=resolve_interpret(interpret),
        name=name,
    )
    return jax.named_scope(spans.UPLINK)(call)


def masked_sum(
    x: jax.Array,  # (n, d) f32 workspace
    slot: jax.Array,  # (n,) int32; outside [0, m) -> contributes nothing
    band: jax.Array,  # (d,) int32 per-coordinate owner band
    m: int,
    s: int,
    *,
    counts: bool = False,
    block: int = 4096,
    interpret: Optional[bool] = None,
):
    """UpCom fused with the 1/s rebuild: ``sum_owned(x, axis=0) / s``.

    With ``counts=True`` (the survivor-aware path) returns the raw
    ``(num, cnt)`` pair instead — the undivided masked sum and the
    per-coordinate arrived-owner count — so the caller can psum both
    and rebuild ``x_bar = num / max(cnt, 1)`` globally."""
    n, d = x.shape
    blk = fit_block(block, d, n, [x.dtype.itemsize])
    if counts:
        kernel = functools.partial(_masked_sum_counts_kernel, m=m, s=s)
    else:
        kernel = functools.partial(_masked_sum_kernel, m=m, s=s)
    return _col_call(kernel, n, d, blk, ["row", "vec", "mat"],
                     2 if counts else 1, 0, interpret,
                     "masked_sum_counts" if counts else "masked_sum")(
                         slot, band, x)


def robust_sum(
    x: jax.Array,  # (n, d) f32 (or float-wire) workspace
    slot: jax.Array,  # (n,) int32; outside [0, m) -> contributes nothing
    band: jax.Array,  # (d,) int32 per-coordinate owner band
    m: int,
    s: int,
    *,
    kind: str,  # "trimmed" | "median"
    k: int = 0,  # values trimmed per side (trimmed only)
    block: int = 4096,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Byzantine-robust UpCom: per-coordinate trimmed mean / median over
    the arrived owner values, in-tile (the ``masked_sum(counts=True)``
    robust sibling).  Returns ``(x_bar, cnt)`` — the already-combined
    value (0 where no owner arrived; callers gate on ``cnt > 0`` exactly
    like the survivor path, and do NOT divide) and the f32 arrived-owner
    count.  Int-wire lanes must be dequantized before the call: robust
    order statistics are defined on dequantized values (DESIGN.md §15).
    """
    if kind not in ("trimmed", "median"):
        raise ValueError(f"robust_sum kind {kind!r}")
    if not (0 <= 2 * int(k) < s):
        if kind == "trimmed":
            raise ValueError(f"robust_sum needs 0 <= 2k < s (k={k}, s={s})")
    n, d = x.shape
    # the s selection passes keep a few (n, blk) temporaries live at once
    blk = fit_block(block, d, n, [x.dtype.itemsize], temps=6)
    kernel = functools.partial(_robust_sum_kernel, m=m, s=s, kind=kind,
                               k=int(k))
    return _col_call(kernel, n, d, blk, ["row", "vec", "mat"], 2, 0,
                     interpret, f"robust_sum_{kind}")(slot, band, x)


def h_update(
    x: jax.Array,  # (n, d) f32 workspace
    h: jax.Array,  # (n, d) f32 control variates
    x_bar: jax.Array,  # (d,) f32 rebuilt server model
    slot: jax.Array,  # (n,) int32
    band: jax.Array,  # (d,) int32
    m: int,
    s: int,
    scale: float,  # eta / gamma
    *,
    down: Optional[jax.Array] = None,  # (n,) int32/bool DownCom targets
    covered: Optional[jax.Array] = None,  # (d,) bool: coord has a survivor
    block: int = 4096,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One fused pass: ``h += scale * owned * (x_bar - x)`` and the DownCom
    ``x_new = x_bar`` on the ``down`` rows (every row when ``down=None``);
    rows outside ``down`` keep their ``x`` bit-exactly.  ``covered``
    (survivor-aware path) additionally masks per-coordinate: coordinates
    with no arrived owner keep both h and x bit-exactly."""
    n, d = x.shape
    blk = fit_block(block, d, n, [4, 4, 4, 4])  # x, h in; h, x out
    down = (jnp.ones((n,), jnp.int32) if down is None
            else down.astype(jnp.int32))
    if covered is not None:
        kernel = functools.partial(
            _h_update_covered_kernel, m=m, s=s, scale=scale
        )
        specs = ["row", "row", "vec", "vec", "vec", "mat", "mat"]
        args = (slot, down, band, covered.astype(jnp.int32), x_bar, x, h)
        name = "h_update_covered"
    else:
        kernel = functools.partial(_h_update_kernel, m=m, s=s, scale=scale)
        specs = ["row", "row", "vec", "vec", "mat", "mat"]
        args = (slot, down, band, x_bar, x, h)
        name = "h_update"
    return _col_call(kernel, n, d, blk, specs, 0, 2, interpret,
                     name)(*args)
