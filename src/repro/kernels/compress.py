"""Pallas TPU kernel: fused TAMUNA mask-generate-and-apply (C_i).

The permutation mask is never materialized in HBM: each VMEM tile computes
its coordinates' ownership from the cyclic-band closed form (masks.py /
paper Fig. 1) and multiplies in place.  VPU-only (no MXU): the kernel is
bandwidth-bound by design — 1 read + 1 write per element instead of the
3 reads + 1 write a materialized-mask path costs.

``owned_from_band`` is the shared ownership predicate of the whole comm
path: the uplink kernels (``kernels/uplink.py``) and the flat-workspace
comm step (``dist/comm_ws.py``) evaluate the same closed form, so this
module's mask generation IS the production comm step's mask generation.

Operands may be flat ``(d,)`` vectors (1-D grid over coordinate blocks,
``slot`` shaped ``(1,)``) or client-stacked ``(n, d)`` matrices (1-D grid
over ``(n, blk)`` coordinate tiles sized by ``fit_block``, ``slot``
shaped ``(n,)`` and read whole by every tile).
``interpret=None`` auto-detects the backend: compiled via Mosaic on TPU,
interpreter elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None -> interpret only off-TPU (Mosaic compile on real TPUs)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def owned_from_band(slot, band, m: int, s: int):
    """Closed-form ownership: active slots in ``[0, m)`` own coordinate
    ``k`` iff ``(slot + band[k]) mod m < s``.  With the cyclic band
    ``band = (-s k) mod c`` this is exactly ``masks.mask_from_permutation``
    row ownership; with the blocked band (chunk ids) it is the block_rs
    closed form.  Shapes broadcast; never materialized outside a tile."""
    return (slot >= 0) & (slot < m) & (((slot + band) % m) < s)


def cyclic_band(k, c: int, s: int):
    """The cyclic template's per-coordinate band: ``(-s k) mod c``."""
    return (-(s * (k % c))) % c


# Scoped VMEM the (n, blk)-tiled kernels ask Mosaic for (v5e has 128 MiB
# per core; Mosaic's default scope is 16 MiB), and the part of it that
# the double-buffered tiles plus the in-tile f32 temporaries may take.
VMEM_LIMIT_BYTES = 48 * 2**20
VMEM_TILE_BYTES = 32 * 2**20
# XLA tiles a 1-D f32/int32 array by 1024 elements on TPU, so a (blk,)
# block of a longer vector must be a multiple of it.
VEC_TILE = 1024


def fit_block(block: int, d: int, n: int, itemsizes, temps: int = 2) -> int:
    """The coordinate block of an ``(n, blk)``-tiled kernel: the widest
    multiple of ``VEC_TILE`` (at most ``block``, at least one tile) at
    which the double-buffered tiles of the ``(n, d)`` operands (one entry
    of ``itemsizes`` each) plus ``temps`` f32 ``(n, blk)`` temporaries
    fit ``VMEM_TILE_BYTES``; rows pad to the 8-sublane tile.  ``d``
    itself when it is narrower (a block equal to the whole axis)."""
    rows = -(-n // 8) * 8
    per_col = rows * (2 * sum(itemsizes) + 4 * temps)
    fit = min(block, VMEM_TILE_BYTES // per_col) // VEC_TILE * VEC_TILE
    blk = max(VEC_TILE, fit)
    return d if d <= blk else blk


# Coordinates of one client row that a leaf-native tile spans at most
# (``fit_tile``): 2 MiB of f32 per operand at n=4, large enough that the
# per-grid-step cost vanishes against the DMA.
LEAF_BLOCK = 1 << 17


def fit_tile(R: int, C: int, n: int, itemsizes, plane_itemsizes,
             temps: int = 6, block: int = LEAF_BLOCK):
    """The ``(r_blk, c_blk)`` block of an ``(n, r_blk, c_blk)``-tiled
    kernel over an ``(n, R, C)`` leaf view: at most ``block`` coordinates
    of a row, and no more than the double-buffered ``(n, ...)`` tiles
    (one entry of ``itemsizes`` each), the ``(r_blk, c_blk)`` plane
    operands (``plane_itemsizes``) and ``temps`` f32 plane temporaries
    fit in ``VMEM_TILE_BYTES``.  ``c_blk`` is ``C`` or a multiple of 128,
    ``r_blk`` is ``R`` or a multiple of the narrowest dtype's sublane
    tile (8 rows of f32, 16 of bf16); a ragged last block is a partial
    grid step."""
    per = n * 2 * sum(itemsizes) + 2 * sum(plane_itemsizes) + 4 * temps
    cap = max(8 * 128, min(block, VMEM_TILE_BYTES // per))
    sub = 32 // min(itemsizes)
    r_min = min(R, sub)
    c_blk = C if C * r_min <= cap else max(128, cap // r_min // 128 * 128)
    r_fit = cap // c_blk
    r_blk = R if R <= r_fit else max(sub, r_fit // sub * sub)
    return r_blk, c_blk


# Mosaic parameters of the ``fit_block``- and ``fit_tile``-tiled kernels
TILED_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def wire_dequant(codes, scales, chunk_ids):
    """Dequantize int-wire payload lanes: ``codes`` (rows, d) int8 times
    the per-chunk f32 scale each column's ``chunk_ids`` entry selects
    from ``scales`` (rows, nchunk).  The one definition of the wire's
    dequantization, shared by the jnp comm paths and, ahead of the
    uplink kernels, the pallas ones, so the kernel and jnp impls cannot
    drift (a NaN-poisoned chunk scale propagates the NaN in both).

    The scales gather reads the flattened scales at ``row * nchunk +
    chunk``, a row-major result; ``take(axis=1)`` lays its output out
    coordinate-major, which the TPU pads along the row axis to 128
    lanes."""
    rows, nchunk = scales.shape
    idx = (jnp.arange(rows, dtype=jnp.int32)[:, None] * nchunk
           + chunk_ids[None, :])
    return codes.astype(jnp.float32) * jnp.take(scales.reshape(-1), idx)


def _compress_kernel(slot_ref, x_ref, o_ref, *, c: int, s: int, block: int):
    i = pl.program_id(0)
    k = jax.lax.broadcasted_iota(jnp.int32, (block,), 0) + i * block
    owned = owned_from_band(slot_ref[0], cyclic_band(k, c, s), c, s)
    x = x_ref[...]
    o_ref[...] = jnp.where(owned, x, jnp.zeros((), x.dtype))


def _compress2d_kernel(slot_ref, x_ref, o_ref, *, c: int, s: int,
                       block: int):
    j = pl.program_id(0)
    k = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1) + j * block
    owned = owned_from_band(slot_ref[...][:, None], cyclic_band(k, c, s),
                            c, s)
    x = x_ref[...]
    o_ref[...] = jnp.where(owned, x, jnp.zeros((), x.dtype))


def compress(
    x: jax.Array,  # (d,) flat or (n, d) client-stacked
    slot: jax.Array,  # (1,)/(n,) int32 mask column(s); outside [0, c) -> 0s
    c: int,
    s: int,
    *,
    block: int = 4096,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    if x.ndim == 2:
        n, d = x.shape
        blk = fit_block(block, d, n, [x.dtype.itemsize] * 2)
        return pl.pallas_call(
            functools.partial(_compress2d_kernel, c=c, s=s, block=blk),
            grid=(pl.cdiv(d, blk),),
            in_specs=[
                pl.BlockSpec((n,), lambda j: (0,)),  # every client's slot
                pl.BlockSpec((n, blk), lambda j: (0, j)),
            ],
            out_specs=pl.BlockSpec((n, blk), lambda j: (0, j)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            compiler_params=TILED_PARAMS,
            interpret=interpret,
            name="compress2d",
        )(slot, x)

    d = x.shape[0]
    blk = min(block, d)
    return pl.pallas_call(
        functools.partial(_compress_kernel, c=c, s=s, block=blk),
        grid=(pl.cdiv(d, blk),),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),  # slot, broadcast to all tiles
            pl.BlockSpec((blk,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((blk,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="compress",
    )(slot, x)
