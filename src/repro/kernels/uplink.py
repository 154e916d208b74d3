"""Pallas TPU kernels: the mask-free fused TAMUNA comm step.

Two families, one ownership predicate (``compress.owned_from_band``
against a per-client ``slot`` vector), evaluated per VMEM tile — no
``(n, d)`` or ``(d, c)`` mask is ever materialized in HBM:

* ``leaf_masked_sum`` / ``leaf_h_update`` run over ONE state leaf in its
  own layout, viewed as ``(n, R, C)`` (``leaf_view``), on a 2-D grid of
  ``(n, r_blk, c_blk)`` tiles (``compress.fit_tile``).  Each tile
  computes its band from the leaf-local coordinate index ``k = r C + c``
  (cyclic ``(-s k) mod m``, blocked ``k // ceil(D/m)``), reads the
  client rows one at a time (slots and DownCom rows from SMEM), and
  h_update writes h and x in place.  No band table and no packed copy:
  the shard engine's route for every f32-wire, mean-combined leaf that
  is not model-sharded (``dist/comm_ws.py``, ``_leaf_routes``).
* ``masked_sum`` / ``robust_sum`` / ``h_update`` run over the packed
  comm workspace: the client-stacked leaves of one wire kind packed to
  an ``(n, d)`` buffer, ownership from a static per-coordinate ``band``
  table.  They serve the quantized wire, robust combiners and
  model-sharded leaves, whose layouts the workspace fixes.

The workspace kernels:

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
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import spans
from repro.kernels.compress import (
    LEAF_BLOCK,
    TILED_PARAMS,
    cyclic_band,
    fit_block,
    fit_tile,
    owned_from_band,
    resolve_interpret,
)

__all__ = ["masked_sum", "robust_sum", "h_update", "leaf_view",
           "leaf_masked_sum", "leaf_h_update"]


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


# --------------------------------------------------------------------------
# leaf-native kernels: one state leaf in its own layout, no band table
# --------------------------------------------------------------------------


def leaf_view(trail: Tuple[int, ...]) -> Tuple[int, int]:
    """``(R, C)`` of a leaf whose client-stacked shape is ``(n, *trail)``:
    ``C`` the minor dim, ``R`` the product of the dims before it (1 for a
    1-D parameter).  The reshape to ``(n, R, C)`` is a bitcast on the TPU
    whenever the second-minor dim is a multiple of the sublane tile."""
    return int(math.prod(trail[:-1])), int(trail[-1]) if trail else 1


def _leaf_band(template: str, m: int, s: int, C: int, D: int,
               shape: Tuple[int, int]):
    """The kernel-convention band of the ``shape`` plane tile at grid step
    ``(i, j)``, from the leaf-local flat index ``k = r * C + c``: the same
    closed forms as the workspace's band tables (cyclic ``(-s k) mod m``,
    blocked ``k // ceil(D/m)``), evaluated in VMEM."""
    r_blk, c_blk = shape
    rr = (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
          + pl.program_id(0) * r_blk)
    cc = (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
          + pl.program_id(1) * c_blk)
    k = rr * C + cc
    if template == "cyclic":
        return cyclic_band(k, m, s)
    return jax.lax.div(k, jnp.int32(-(-D // m)))


def _row_owned(slot_ref, i, band, m: int, s: int):
    """Row ``i``'s ownership of the tile: its SMEM slot against the band."""
    return owned_from_band(jnp.full(band.shape, slot_ref[i], jnp.int32),
                           band, m, s)


def _leaf_masked_sum_kernel(slot_ref, x_ref, *out_refs, n: int, m: int,
                            s: int, band_of, counts: bool):
    band = band_of(out_refs[0].shape)
    zero = jnp.zeros(band.shape, jnp.float32)

    def row(i, acc):
        num, cnt = acc
        owned = _row_owned(slot_ref, i, band, m, s)
        num = num + jnp.where(owned, x_ref[i].astype(jnp.float32), 0.0)
        if counts:
            cnt = cnt + owned.astype(jnp.float32)
        return num, cnt

    num, cnt = jax.lax.fori_loop(0, n, row, (zero, zero),
                                 unroll=min(n, 8))
    if counts:
        out_refs[0][...] = num
        out_refs[1][...] = cnt
    else:
        out_refs[0][...] = num / s


def _leaf_h_update_kernel(slot_ref, down_ref, *refs, n: int, m: int, s: int,
                          scale: float, band_of, covered: bool):
    if covered:
        cov_ref, xbar_ref, x_ref, h_ref, h_out, x_out = refs
    else:
        xbar_ref, x_ref, h_ref, h_out, x_out = refs
    x_bar = xbar_ref[...]
    band = band_of(x_bar.shape)
    cov = cov_ref[...] != 0 if covered else None
    bar = x_bar.astype(x_out.dtype)

    def row(i, carry):
        owned = _row_owned(slot_ref, i, band, m, s)
        dn = jnp.full(band.shape, down_ref[i], jnp.int32) != 0
        if cov is not None:
            owned, dn = owned & cov, dn & cov
        x = x_ref[i]
        h_out[i] = (h_ref[i].astype(jnp.float32) + scale * jnp.where(
            owned, x_bar - x.astype(jnp.float32), 0.0)).astype(h_out.dtype)
        x_out[i] = jnp.where(dn, bar, x)
        return carry

    jax.lax.fori_loop(0, n, row, 0, unroll=min(n, 8))


def _leaf_call(kernel, shape, tile, in_kinds, outs, interpret, name: str,
               aliases=None):
    """``pallas_call`` over a 2-D grid of ``tile`` plane blocks of an
    ``(n, R, C)`` leaf view of ``shape``.  ``in_kinds`` entries are
    ``"smem"`` ((n,) int32 whole, in SMEM: read a row at a time),
    ``"plane"`` ((r_blk, c_blk) block of an (R, C) array) or ``"leaf"``
    ((n, r_blk, c_blk) block); ``outs`` are ``(kind, dtype)`` pairs of
    the last two.  Runs under the ``tamuna.uplink`` scope and carries the
    kernel's ``name``."""
    n, R, C = shape
    r_blk, c_blk = tile
    spec = {
        "smem": pl.BlockSpec(memory_space=pltpu.SMEM),
        "plane": pl.BlockSpec((r_blk, c_blk), lambda i, j: (i, j)),
        "leaf": pl.BlockSpec((n, r_blk, c_blk), lambda i, j: (0, i, j)),
    }
    dims = {"plane": (R, C), "leaf": (n, R, C)}
    call = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(R, r_blk), pl.cdiv(C, c_blk)),
        in_specs=[spec[k] for k in in_kinds],
        out_specs=tuple(spec[k] for k, _ in outs),
        out_shape=tuple(jax.ShapeDtypeStruct(dims[k], dt) for k, dt in outs),
        input_output_aliases=aliases or {},
        compiler_params=TILED_PARAMS,
        interpret=resolve_interpret(interpret),
        name=name,
    )
    return jax.named_scope(spans.UPLINK)(call)


def _band_of(template: str, m: int, s: int, R: int, C: int):
    if template not in ("cyclic", "blocked"):
        raise ValueError(f"unknown template {template!r}")
    if R * C >= 2**31:
        raise ValueError(f"leaf of {R * C} coordinates: int32 index")
    return functools.partial(_leaf_band, template, m, s, C, R * C)


def leaf_masked_sum(
    x: jax.Array,  # (n, R, C) leaf view (``leaf_view``), any float dtype
    slot: jax.Array,  # (n,) int32; outside [0, m) -> contributes nothing
    m: int,
    s: int,
    *,
    template: str,  # "cyclic" | "blocked"
    counts: bool = False,
    block: int = LEAF_BLOCK,
    interpret: Optional[bool] = None,
):
    """``masked_sum`` over one leaf in its own layout: ownership from the
    leaf-local coordinate index inside the kernel (no band table), the
    client-axis sum and the ``1/s`` rebuild in f32.  Returns the ``(R,
    C)`` x_bar, or with ``counts=True`` the raw ``(num, cnt)`` pair (see
    ``masked_sum``)."""
    n, R, C = x.shape
    n_out = 2 if counts else 1
    tile = fit_tile(R, C, n, [x.dtype.itemsize], [4] * n_out, block=block)
    kernel = functools.partial(
        _leaf_masked_sum_kernel, n=n, m=m, s=s,
        band_of=_band_of(template, m, s, R, C), counts=counts)
    out = _leaf_call(kernel, x.shape, tile, ["smem", "leaf"],
                     [("plane", jnp.float32)] * n_out, interpret,
                     "leaf_masked_sum_counts" if counts
                     else "leaf_masked_sum")(slot, x)
    return tuple(out) if counts else out[0]


def leaf_h_update(
    x: jax.Array,  # (n, R, C) leaf view
    h: jax.Array,  # (n, R, C) control variates
    x_bar: jax.Array,  # (R, C) f32 rebuilt server model
    slot: jax.Array,  # (n,) int32
    m: int,
    s: int,
    scale: float,  # eta / gamma
    *,
    template: str,
    down: Optional[jax.Array] = None,  # (n,) int32/bool DownCom targets
    covered: Optional[jax.Array] = None,  # (R, C) bool: coord has a survivor
    block: int = LEAF_BLOCK,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """``h_update`` over one leaf in its own layout, h and x updated in
    place (x aliases x_new, h aliases h_new): ``h += scale * owned *
    (x_bar - x)`` in f32 and the DownCom ``x_new = x_bar`` on the
    ``down`` rows, in each leaf's storage dtype.  ``covered`` gates both
    per coordinate as in ``h_update``."""
    n, R, C = x.shape
    planes = [4] * (2 if covered is not None else 1)
    tile = fit_tile(R, C, n, [x.dtype.itemsize, h.dtype.itemsize] * 2,
                    planes, block=block)
    down = (jnp.ones((n,), jnp.int32) if down is None
            else down.astype(jnp.int32))
    args = [slot, down]
    if covered is not None:
        args.append(covered.astype(jnp.int32))
    args += [x_bar, x, h]
    kernel = functools.partial(
        _leaf_h_update_kernel, n=n, m=m, s=s, scale=scale,
        band_of=_band_of(template, m, s, R, C),
        covered=covered is not None)
    kinds = ["smem", "smem"] + ["plane"] * len(planes) + ["leaf", "leaf"]
    ix = len(args) - 2  # x, then h: updated in place
    return _leaf_call(
        kernel, x.shape, tile, kinds,
        [("leaf", h.dtype), ("leaf", x.dtype)], interpret,
        "leaf_h_update_covered" if covered is not None else "leaf_h_update",
        aliases={ix: 1, ix + 1: 0})(*args)
