"""Mask-free comm step: sparse closed-form uplinks + the flat workspace.

The reference comm step walks the client-stacked state leaf by leaf and
materializes a dense ``(n, D)`` ownership mask per leaf, multiplies it in,
and reduces over all ``n`` client rows — the memory-traffic profile of an
*uncompressed* round, exactly the cost TAMUNA's sparse templates exist to
avoid.  This module replaces it with two mask-free implementations that
compute ownership on the fly from static per-coordinate tables, plus the
dense path itself (``impl="dense"``) kept as the property-tested ground
truth:

``impl="ws"`` — the sparse fused path (production default off-TPU).
  Every coordinate has exactly ``s`` owners at *closed-form* positions
  (template row property), so UpCom never has to scan the client axis:

    x_bar[k] = (1/s) * sum_t  x[owner_row(t, k), k]

  is ``s`` row-gathers per leaf — ``O(s d)`` reads, independent of ``n``
  (``owner_row`` = a static ``(s, D)`` column table pushed through the
  round's column->client scatter for the cyclic template, or the shifted
  block ids for the blocked template).  The h-update + DownCom broadcast
  are one fused elementwise pass per leaf with the ownership predicate
  ``(slot - band[k]) mod m < s`` evaluated inside the fusion off a static
  int32 band table — never materialized.  Measured on the 2-core CPU host
  (BENCH_comm_step.json): the dense reference's extra mask passes grow
  with ``n`` while this path stays at the read-x/read-h/write-h/write-x
  floor, ~2 passes over ``(n, d_total)`` state.

  ``meshed=True`` (what ``make_comm_step`` passes): when the client axis
  is *sharded across devices*, the owner rows live on other shards and
  GSPMD turns a row-gather into an ``(n, d)``-sized all-reduce (measured
  2-4x the collective bytes and 2.5x the wall time of the dense path on
  the 4x2 host mesh).  Meshed mode therefore keeps the UpCom in the
  d-sized-psum shape — the minimal collective — with the ownership
  predicate fused into the local partial sum, and the sparse gathers are
  reserved for unsharded stacked state (the bench, single-device sims).

``impl="pallas"`` — the workspace kernel path (TPU production).
  Unsharded state: all leaves packed once into a single ``(n, d_total)``
  f32 buffer with a static leaf-offset table (``WorkspaceSpec``), then two
  Pallas kernels (``repro.kernels.uplink``) do the whole comm math:
  ``masked_sum`` (per-VMEM-tile ownership fused with the ``1/s`` rebuild)
  and ``h_update`` (reads x, h, x_bar once; writes h_new AND the broadcast
  x_new in the same pass).  No ``(n, d)`` or ``(d, c)`` mask exists at any
  point in the lowering (regression-tested in tests/test_comm_ws.py).  On
  CPU the kernels run in interpret mode (correctness smokes only: the
  interpreter unrolls the grid, and the pack itself costs a full
  read+write pass that XLA's leafwise fusion avoids — measured, see
  DESIGN.md §9 — which is why ``auto`` resolves to ``"ws"`` off-TPU).

  ``meshed=True`` + a ``mesh`` handle: the **shard-resident engine**
  (DESIGN.md §10).  The whole comm step runs inside ``shard_map`` over
  the client-hosting (dp) mesh axes, each shard on its *local* client
  rows, and every leaf takes one of three routes (``_leaf_routes``,
  decided by what the call shows; ``route_coords`` counts them):

    leaf       the TPU path for the f32 wire: the leaf-native kernels
               (``uplink.leaf_masked_sum`` / ``leaf_h_update``) reduce
               and update each leaf of ``LEAF_MIN`` coordinates a row or
               more in its own layout, ownership from the leaf-local
               coordinate index inside the kernel — no band table, no
               packed copy, x and h updated in place;
    workspace  the per-shard packed workspace and its kernels, now only
               for the quantized wire (its chunk scales lie over the
               packed flat axis), robust combiners on one client shard,
               and model-sharded leaves (bands from the global index);
    jnp        fused jnp per leaf — coarse per-block chunk gathers for
               the blocked template, masked local partials for the
               cyclic one: leaves under ``LEAF_MIN``, tall leaves, robust
               leaves across client shards, and every leaf off-TPU.

  The shards combine with d-sized ``psum``s of the ``1/s``-folded
  partials — one per workspace, per leaf on the other routes — the
  reduce-scatter-shaped minimum, never an ``(n, d)``-sized collective.
  The h-update/DownCom then run per shard on local rows reading the
  combined ``x_bar`` once.  Ownership bands for model-sharded leaves are
  recomputed per shard from the global coordinate index
  (``sharding.spec_dim_axes`` offsets), so tensor parallelism keeps its
  d/model-sized partial.  ``effective_impl("pallas", meshed=True,
  mesh=...)`` runs this engine.

One band table encodes BOTH templates:

  cyclic   band[k] = (s * k_leaf) mod c,   m = c,
           slot[i] = template column of client i's cohort slot
           (``perm[slot_of[i]]``, -1 when idle) — coordinate-identical to
           ``masks.mask_from_permutation`` per leaf (both Fig. 1 regimes;
           the tall-and-thin regime ``D s < c`` keeps its own closed form
           on the ``ws`` path and falls back to dense under ``pallas``),
  blocked  band[k] = k_leaf // ceil(D/m),  m = c (the COHORT size — ``n``
           under full participation), ownership
           ``(band[k] - slot_of[i] - off) mod c < s``: the contiguous
           per-block bands laid over the round's c cohort *slots*, so the
           reduce-scatter-shaped uplink works at any ``c <= n``
           (DESIGN.md §11); idle clients (``slot_of = -1``) own nothing.

Both templates take an optional ``down`` row mask: the DownCom writes
``x_bar`` only to those rows (the NEXT round's cohort under elastic
partial participation — idle clients' ``x`` passes through bit-exactly);
``down=None`` broadcasts to every row, the full-participation behaviour.

Fault tolerance (DESIGN.md §12): both templates also take an optional
``arrived`` mask over the client rows — a cohort member whose uplink never
lands is demoted to idle (``slot = -1``: owns nothing, contributes
nothing, NaN payloads included).  With ``correct=True`` (survivor-aware
aggregation) the exact ``1/s`` rebuild becomes the per-coordinate
``1/(arrived owner count)`` — unbiased whenever dropout is independent of
the payload — and *uncovered* coordinates (every owner dropped) are left
bitwise untouched in BOTH h and x, extending §11's idle-row semantics to
single coordinates; ``correct=False`` keeps the ``1/s`` division and the
full DownCom (the biased wait-all-with-drops control the fault benchmark
measures against).  Under an all-``True`` arrival mask the corrected path
computes bit-identical values to ``arrived=None`` on the dense and ws
paths; the kernel path's two-output counts kernel lets XLA reassociate
the client-axis reduction (≤1 ulp — which is why the round driver passes
``arrived=None`` outright for a zero-fault plan, keeping the program
itself identical).

All functions are pure jnp over the stacked client axis (mesh-free and
mesh-agnostic); callers pick ``meshed`` per placement, and ``impl`` per
backend (``resolve_impl``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.dist import robust as _robust
from repro.dist import wire as _wire

__all__ = [
    "WorkspaceSpec",
    "workspace_spec",
    "pack",
    "unpack",
    "resolve_impl",
    "effective_impl",
    "COMM_IMPLS",
    "cyclic_comm",
    "blocked_comm",
    "uncovered_coords",
]

COMM_IMPLS = ("auto", "dense", "ws", "pallas")


def resolve_impl(impl: Optional[str]) -> str:
    """``auto`` -> Pallas workspace kernels on TPU, sparse fused jnp
    elsewhere (see module docstring for the measured rationale)."""
    impl = impl or "auto"
    if impl not in COMM_IMPLS:
        raise ValueError(f"unknown comm impl {impl!r}; want one of "
                         f"{COMM_IMPLS}")
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ws"
    return impl


def effective_impl(impl: Optional[str], *, meshed: bool = False,
                   mesh=None) -> str:
    """The impl that will actually execute.  ``pallas`` on a meshed
    placement runs the shard-resident engine (shard_map'd per-shard
    kernels + one d-sized psum of the partials, DESIGN.md §10), which
    needs the mesh handle for its axis names; a meshed call *without* a
    mesh falls back to the psum-shaped ``ws`` path (the pre-shard_map
    behaviour).  The single source of truth for that rule — launch
    reporting uses it too (pass the mesh there)."""
    impl = resolve_impl(impl)
    if impl == "pallas" and meshed and mesh is None:
        return "ws"
    return impl


# --------------------------------------------------------------------------
# workspace pack / unpack (the Pallas path's layout)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WorkspaceSpec:
    """Static leaf-offset table of a packed ``(n, d_total)`` workspace.

    Under the shard-resident engine the spec describes ONE shard's
    resident block: ``n``/``dims``/``offsets`` are the shard-local row
    count and flat-axis layout (built from the shard's local leaves inside
    the ``shard_map`` body), while ``rows_total`` records the global
    client-row count the blocks tile (``rows_total == n`` off-mesh)."""

    n: int
    shapes: Tuple[tuple, ...]  # stacked shapes (n, *param), shard-local
    dtypes: Tuple[Any, ...]  # storage dtypes, restored by unpack
    dims: Tuple[int, ...]  # flattened per-leaf param dims D
    offsets: Tuple[int, ...]  # leaf start offsets in the flat axis
    d_total: int
    rows_total: int = -1  # global client rows (== n when unsharded)
    wire_kinds: Tuple[str, ...] = ()  # per-leaf wire kind (empty: all f32)


def workspace_spec(
    leaves: Sequence[Any], rows_total: Optional[int] = None,
    wire: Optional[str] = None, wire_dims: Optional[Sequence[int]] = None,
) -> WorkspaceSpec:
    """Offset table for a list of stacked leaves (arrays or structs).
    ``rows_total`` marks a shard-local spec with the global row count.
    ``wire`` resolves the size-adaptive per-leaf wire precision at spec
    build time (``dist/wire.py``): ``wire_kinds[i]`` is leaf i's payload
    dtype on the UpCom wire.  ``wire_dims`` overrides the leaf sizes the
    policy sees (the GLOBAL dims under the shard engine, where the local
    block is smaller than the leaf)."""
    shapes = tuple(tuple(a.shape) for a in leaves)
    dims = tuple(int(np.prod(s[1:])) for s in shapes)
    offsets = tuple(int(o) for o in np.cumsum((0,) + dims)[:-1])
    n = int(shapes[0][0]) if shapes else 0
    pdims = tuple(wire_dims) if wire_dims is not None else dims
    return WorkspaceSpec(
        n=n,
        shapes=shapes,
        dtypes=tuple(a.dtype for a in leaves),
        dims=dims,
        offsets=offsets,
        d_total=int(sum(dims)),
        rows_total=n if rows_total is None else int(rows_total),
        wire_kinds=tuple(_wire.resolve_kind(D, wire) for D in pdims),
    )


def pack(leaves: Sequence[jax.Array], spec: WorkspaceSpec) -> jax.Array:
    """All leaves -> one ``(n, d_total)`` f32 buffer (a single fused op;
    under donation the leaf buffers are dead immediately after)."""
    flat = [
        a.reshape(spec.n, -1).astype(jnp.float32) for a in leaves
    ]
    return flat[0] if len(flat) == 1 else jnp.concatenate(flat, axis=1)


def unpack(ws: jax.Array, spec: WorkspaceSpec) -> List[jax.Array]:
    """``(n, d_total)`` buffer -> leaves in storage dtype/shape."""
    return [
        ws[:, o:o + d].astype(dt).reshape(sh)
        for o, d, dt, sh in zip(spec.offsets, spec.dims, spec.dtypes,
                                spec.shapes)
    ]


# --------------------------------------------------------------------------
# static per-coordinate tables (cached on the leaf-dim signature)
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _cyclic_leaf_tables_np(D: int, c: int, s: int):
    """(owner-column table (s, D), band (D,), tall?) for one leaf.

    cols[t, k] = the t-th template column owning coordinate k: the cyclic
    band ``(s k + t) mod c`` when ``D s >= c`` (paper Fig. 1 left), else
    the tall-and-thin columns ``k + t D`` (all < D s <= c; columns past
    ``D s`` own nothing).  band[k] = (s k) mod c drives the ownership
    predicate of the cyclic regime."""
    k = np.arange(D, dtype=np.int64)
    tall = D * s < c
    if tall:
        cols = np.stack([k + t * D for t in range(s)])
    else:
        cols = np.stack([(s * k + t) % c for t in range(s)])
    band = ((s * k) % c).astype(np.int32)
    return cols.astype(np.int32), band, tall


@functools.lru_cache(maxsize=None)
def _block_leaf_band_np(D: int, n: int) -> np.ndarray:
    """band[k] = k // ceil(D/n): the leaf-local chunk (block) id."""
    return (np.arange(D, dtype=np.int64) // (-(-D // n))).astype(np.int32)


@functools.lru_cache(maxsize=None)
def _cyclic_band_np(dims: Tuple[int, ...], c: int, s: int) -> np.ndarray:
    """Packed-workspace band: (-s * k_leaf) mod c per coordinate, so the
    kernels' shared ``(slot + band) mod m < s`` predicate applies."""
    parts = [
        ((-(s * (np.arange(D, dtype=np.int64) % c))) % c).astype(np.int32)
        for D in dims
    ]
    return np.concatenate(parts) if parts else np.zeros((0,), np.int32)


@functools.lru_cache(maxsize=None)
def _block_band_np(dims: Tuple[int, ...], n: int) -> np.ndarray:
    """Packed-workspace block ids (leaf-local chunking)."""
    parts = [_block_leaf_band_np(D, n) for D in dims]
    return np.concatenate(parts) if parts else np.zeros((0,), np.int32)


@functools.lru_cache(maxsize=None)
def _cyclic_band_counts_np(D: int, c: int, s: int) -> np.ndarray:
    """(c,) int64: coordinates per cyclic band value ``(s k) mod c``
    (non-tall regime only)."""
    band = (s * np.arange(D, dtype=np.int64)) % c
    return np.bincount(band, minlength=c)


@functools.lru_cache(maxsize=None)
def _block_band_counts_np(D: int, m: int) -> np.ndarray:
    """(m,) int64: coordinates per block id for one leaf."""
    return np.bincount(_block_leaf_band_np(D, m), minlength=m)


def uncovered_coords(template: str, dims: Tuple[int, ...], m: int, s: int,
                     slot: jax.Array) -> jax.Array:
    """int32 scalar: coordinates with NO surviving owner this round.

    ``slot`` is the per-client final slot/column assignment the comm step
    aggregates with (``-1`` = idle or demoted by the arrival mask): the
    cyclic template column for ``template="cyclic"`` or the folded
    ``(-(slot_of + off)) mod c`` blocked slot for ``template="blocked"``.
    Under the survivor-aware rebuild (DESIGN.md §12) exactly these
    coordinates pass through ``x``/``h`` bitwise untouched, so the count
    is the per-round coverage loss the bounded-staleness driver traces
    (§14) — dropped-late uplinks show up here, admitted ones don't.

    Pure jnp over the (m,) slot-occupancy vector plus static per-leaf
    band counts; O(s·m + tall-leaf coords) device work, no dependence on
    the payload itself."""
    if template not in ("cyclic", "blocked"):
        raise ValueError(f"unknown template {template!r}")
    slot = jnp.asarray(slot, jnp.int32)
    # slot-value occupancy; -1 rows land in the m overflow cell
    pres = jnp.zeros((m + 1,), bool).at[
        jnp.where(slot >= 0, slot, m)
    ].set(True)[:m]
    total = jnp.int32(0)
    if template == "cyclic":
        # covered band b iff any owner column (b + t) mod c, t < s, has an
        # arriving client; tall leaves (D s < c) use their explicit
        # owner-column table instead (cols k + t D)
        cov_band = jnp.zeros((m,), bool)
        for t in range(s):
            cov_band = cov_band | jnp.roll(pres, -t)
        for D in dims:
            cols, _, tall = _cyclic_leaf_tables_np(D, m, s)
            if tall:
                cov = pres[jnp.asarray(cols)].any(axis=0)
                total = total + (D - cov.sum()).astype(jnp.int32)
            else:
                cnt = jnp.asarray(_cyclic_band_counts_np(D, m, s))
                total = total + jnp.where(
                    cov_band, 0, cnt
                ).sum().astype(jnp.int32)
    else:
        # blocked ownership is (slot + block) mod m < s, so block b is
        # covered iff any arriving slot value equals (t - b) mod m
        pres_rev = jnp.roll(pres[::-1], 1)  # pres_rev[b] = pres[(-b) % m]
        cov_band = jnp.zeros((m,), bool)
        for t in range(s):
            cov_band = cov_band | jnp.roll(pres_rev, t)
        for D in dims:
            cnt = jnp.asarray(_block_band_counts_np(D, m))
            total = total + jnp.where(
                cov_band, 0, cnt
            ).sum().astype(jnp.int32)
    return total


# --------------------------------------------------------------------------
# quantized wire (dist/wire.py fused into every impl — DESIGN.md §13)
#
# The one rule all four impls share: quantization is a PER-ROW function of
# the leaf payload (row r's wire values depend only on row r, keyed on
# (round seed, leaf, global row id, leaf coordinate id)), applied to the
# UpCom numerator ONLY — the h-update and the DownCom passthrough read the
# raw f32 payload, mirroring the convergence-validated core path
# (core/tamuna.py: X_up feeds aggregate_masked, h updates against X).
# Q(0) == 0 exactly, so idle/faulted rows need no special casing, and the
# survivor-aware 1/(arrived owner count) rebuild divides AFTER
# dequantization — PR 6's fault semantics are unchanged.
# --------------------------------------------------------------------------


def _wire_policy(wire: Optional[str]) -> Optional[str]:
    """None/"f32" -> None: the f32 path takes the PR 6 code verbatim."""
    return wire if _wire.is_wire(wire) else None


def _wire_seed(wire_seed) -> jax.Array:
    if wire_seed is None:
        return jnp.uint32(0)
    return jnp.asarray(wire_seed).astype(jnp.uint32)


def _leaf_quant(kind, seed, li, D, row0=None, coords=None, axes=()):
    """Closure quantize-dequantizing one leaf's ``(rows, D_local)`` f32
    payload at ``kind`` (None when the leaf stays f32).  ``coords`` is
    the block's GLOBAL coordinate index for model-sharded leaves (``D``
    is the global leaf dim there, ``axes`` its model mesh axes);
    ``row0`` offsets the global client-row ids under the shard engine."""
    if kind == "f32":
        return None
    sl = _wire.fold_seed(seed, li)

    def quant(xf):
        rid = jnp.arange(xf.shape[0], dtype=jnp.int32)
        if row0 is not None:
            rid = rid + row0
        rid = rid.astype(jnp.uint32)[:, None]
        kk = (jnp.arange(D, dtype=jnp.int32) if coords is None else coords)
        if kind in _wire.LEVELS and coords is not None:
            scales = _wire.leaf_scales_at(
                xf, kk, _wire.n_chunks(D), kind, axes
            )
            return _wire.quantize(
                xf, kind, sl, rid, kk, scales, kk // _wire.CHUNK
            )
        return _wire.quantize(xf, kind, sl, rid, kk)

    return quant


def _down_quant(kind, seed, li, D, coords=None, axes=()):
    """The DownCom broadcast quantizer (LoCoDL-style bidirectional
    compression): ONE shared quantization of ``x_bar`` per leaf — a
    pseudo row id keys the draw, independent of every uplink row — so
    all clients apply the same ``Q(x_bar)`` and the control-variate
    invariant holds with ``x_bar`` replaced by ``Q(x_bar)``."""
    if kind == "f32":
        return None
    sl = _wire.fold_seed(seed, li)

    def quant(xb):
        x2 = xb[None, :]
        rid = jnp.full((1, 1), _wire.DOWN_ROW, jnp.uint32)
        kk = (jnp.arange(D, dtype=jnp.int32) if coords is None else coords)
        if kind in _wire.LEVELS and coords is not None:
            scales = _wire.leaf_scales_at(
                x2, kk, _wire.n_chunks(D), kind, axes
            )
            return _wire.quantize(
                x2, kind, sl, rid, kk, scales, kk // _wire.CHUNK
            )[0]
        return _wire.quantize(x2, kind, sl, rid, kk)[0]

    return quant


def _make_xbar_tx(offsets, ldims, gdims, idxs, kinds, seed,
                  coords=None, axes=None):
    """Workspace-level DownCom quantizer: split the flat ``x_bar`` at the
    packed leaf offsets, quantize each leaf with its own kind/seed, and
    re-concatenate.  ``ldims`` are the packed (local) dims, ``gdims`` the
    global leaf dims the chunk layout follows."""
    def tx(xb):
        parts = []
        for j, i in enumerate(idxs):
            dq = _down_quant(
                kinds[i], seed, i, gdims[j],
                None if coords is None else coords[j],
                () if axes is None else axes[j],
            )
            seg = xb[offsets[j]:offsets[j] + ldims[j]]
            parts.append(seg if dq is None else dq(seg))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return tx


@functools.lru_cache(maxsize=None)
def _wire_chunkcol_np(dims: Tuple[int, ...]) -> np.ndarray:
    """Packed-workspace scale-column table: per coordinate, the column of
    the concatenated per-leaf chunk-scale array its dequant reads."""
    parts, off = [], 0
    for D in dims:
        parts.append(np.arange(D, dtype=np.int64) // _wire.CHUNK + off)
        off += _wire.n_chunks(D)
    return (np.concatenate(parts) if parts
            else np.zeros((0,), np.int64)).astype(np.int32)


def _wire_pack(flats, leaf_ids, gdims, kind, seed, row0=None,
               coords=None, axes=None):
    """Pack one kind-group's wire payload from per-leaf f32 ``(rows, D)``
    matrices.  Float kinds: one narrow-dtype lane buffer (scales/chunk
    table None).  Int kinds: ``(rows, d)`` int8 codes + ``(rows,
    nchunk_total)`` scales + the ``(d,)`` scale-column table.  ``gdims``
    are the GLOBAL leaf dims (the chunk layout); ``coords``/``axes``
    handle model-sharded blocks under the shard engine."""
    if kind in ("bf16", "f16"):
        vals = [_wire.narrow(f, kind) for f in flats]
        w = vals[0] if len(vals) == 1 else jnp.concatenate(vals, axis=1)
        return w, None, None
    codes_l, scales_l, chunk_l = [], [], []
    for j, f in enumerate(flats):
        D = gdims[j]
        sl = _wire.fold_seed(seed, leaf_ids[j])
        rid = jnp.arange(f.shape[0], dtype=jnp.int32)
        if row0 is not None:
            rid = rid + row0
        rid = rid.astype(jnp.uint32)[:, None]
        kk = None if coords is None else coords[j]
        if kk is None:
            kk = jnp.arange(D, dtype=jnp.int32)
            scales = _wire.leaf_scales(f, kind)
        else:
            scales = _wire.leaf_scales_at(
                f, kk, _wire.n_chunks(D), kind,
                () if axes is None else axes[j],
            )
        cc = kk // _wire.CHUNK
        q, sc = _wire.quantize_to_int(f, kind, sl, rid, kk, scales, cc)
        codes_l.append(q)
        scales_l.append(sc)
        chunk_l.append(cc)
    static = coords is None or all(k is None for k in coords)
    if static:
        chunkcol = jnp.asarray(_wire_chunkcol_np(tuple(gdims)))
    else:
        off = np.cumsum([0] + [_wire.n_chunks(D) for D in gdims])[:-1]
        chunkcol = jnp.concatenate([
            c + jnp.int32(int(o)) for c, o in zip(chunk_l, off)
        ])
    codes = (codes_l[0] if len(codes_l) == 1
             else jnp.concatenate(codes_l, axis=1))
    scales = (scales_l[0] if len(scales_l) == 1
              else jnp.concatenate(scales_l, axis=1))
    return codes, scales, chunkcol


# --------------------------------------------------------------------------
# dense per-leaf reference (the old comm-step math, kept as ground truth)
# --------------------------------------------------------------------------


def _dense_blocked_leaf(xl, hl, slot, m: int, s: int, scale, down=None,
                        sanitize=False, survivor=False, quant=None,
                        down_quant=None, robust=None):
    """One leaf of the dense-mask blocked reference: materialized
    ``(n, D)`` ownership (``(slot_i + block(k)) mod m < s``, the shifted
    blocked template over the ``m`` cohort slots — under full
    participation ``slot_i = (-(i + off)) mod n`` recovers the original
    ``(block(k) - i - off) mod n < s``; idle rows ``slot = -1`` own
    nothing), masked sum over all client rows, 1/s rebuild, masked
    h-update, DownCom.  ``sanitize`` zeroes idle rows before the
    multiply-mask math (this path multiplies by ``qf`` instead of
    selecting, and ``NaN * 0 = NaN`` — a dropped client's corrupted
    payload would otherwise poison x_bar); ``survivor`` switches to the
    per-coordinate arrived-owner-count rebuild.  ``quant`` quantizes the
    UpCom payload (after the sanitize zeroing; h reads the raw rows) and
    ``down_quant`` the rebuilt broadcast — see the wire section above."""
    n = xl.shape[0]
    D = int(np.prod(xl.shape[1:]))
    with jax.named_scope(spans.UPLINK):
        band = jnp.asarray(_block_leaf_band_np(D, m))[None, :]  # (1, D)
        sl = slot[:, None]
        qf = ((sl >= 0) & (((sl + band) % m) < s)).astype(jnp.float32)
        xf = xl.reshape(n, D).astype(jnp.float32)
        if sanitize:
            xf = jnp.where(sl >= 0, xf, 0.0)
        xq = xf if quant is None else quant(xf)
        if robust is not None:
            # robust combine over the dense owner stack: the (n, D) mask IS
            # the validity mask (robust stats on dequantized values, §13)
            x_bar, rcnt = _robust.robust_combine_stack(xq, qf > 0, *robust)
            covered = (rcnt > 0) if survivor else None
        elif survivor:
            x_bar, covered = _survivor_bar((xq * qf).sum(axis=0),
                                           qf.sum(axis=0))
        else:
            x_bar, covered = (xq * qf).sum(axis=0) / s, None
        if down_quant is not None:
            x_bar = down_quant(x_bar)
        h_new = hl.reshape(n, D).astype(jnp.float32) + scale * qf * (
            x_bar[None] - xf
        )
    return (
        _downcom(xl, x_bar, down, covered),
        h_new.astype(hl.dtype).reshape(hl.shape),
    )


def _dense_cyclic_leaf(xl, hl, slot, c: int, s: int, scale, down=None,
                       sanitize=False, survivor=False, quant=None,
                       down_quant=None, robust=None):
    """One leaf of the reference masked_psum comm step: materialized
    ``(n, D)`` mask (both template regimes of paper Fig. 1), masked sum,
    1/s rebuild, masked h-update, broadcast.  The mask is derived from the
    property-tested ``masks.mask_from_permutation`` (identity permutation:
    ``slot`` already IS the template column), so this ground truth never
    drifts from the algorithm spec the fused paths are tested against.
    ``sanitize``/``survivor``/``quant``/``down_quant``: see
    ``_dense_blocked_leaf``."""
    from repro.core import masks  # jax/np only; no x64 side effect

    n = xl.shape[0]
    D = int(np.prod(xl.shape[1:]))
    with jax.named_scope(spans.UPLINK):
        sl = slot[:, None]
        q = masks.mask_from_permutation(
            jnp.arange(c, dtype=jnp.int32), D, c, s
        ).astype(bool)  # (D, c) template
        qf = (
            q.T[jnp.clip(slot, 0)] & (sl >= 0) & (sl < c)
        ).astype(jnp.float32)
        xf = xl.reshape(n, D).astype(jnp.float32)
        if sanitize:
            xf = jnp.where(sl >= 0, xf, 0.0)
        xq = xf if quant is None else quant(xf)
        if robust is not None:
            # robust combine over the dense owner stack: the (n, D) mask IS
            # the validity mask (robust stats on dequantized values, §13)
            x_bar, rcnt = _robust.robust_combine_stack(xq, qf > 0, *robust)
            covered = (rcnt > 0) if survivor else None
        elif survivor:
            x_bar, covered = _survivor_bar((xq * qf).sum(axis=0),
                                           qf.sum(axis=0))
        else:
            x_bar, covered = (xq * qf).sum(axis=0) / s, None
        if down_quant is not None:
            x_bar = down_quant(x_bar)
        h_new = hl.reshape(n, D).astype(jnp.float32) + scale * qf * (
            x_bar[None] - xf
        )
    return (
        _downcom(xl, x_bar, down, covered),
        h_new.astype(hl.dtype).reshape(hl.shape),
    )


# --------------------------------------------------------------------------
# the sparse fused path (impl="ws")
# --------------------------------------------------------------------------


def _wrapped_lt(diff, m: int, s: int):
    """Branch-free ``diff mod m < s`` for ``diff in (-m, m)``: integer mod
    lowers to a hardware divide per element on CPU; two compares don't."""
    return ((diff >= 0) & (diff < s)) | (diff < s - m)


def _wrapped_owned(slot2, band, m: int, s: int):
    """Kernel-convention ownership ``(slot + band) mod m < s`` as two
    compares (no per-element integer divide), idle rows (``slot < 0``)
    excluded.  ``slot2`` broadcasts against ``band``; both in ``[0, m)``."""
    sb = slot2 + band
    return (slot2 >= 0) & (slot2 < m) & (
        (sb < s) | ((sb >= m) & (sb < m + s))
    )


def _downcom(xl, x_bar, down, covered=None):
    """DownCom of one leaf: ``down`` rows (all when None) receive
    ``x_bar`` in storage dtype; every other row keeps its ``x``
    bit-exactly (idle clients under elastic PP, DESIGN.md §11).
    ``covered`` additionally gates per coordinate: columns with no
    arrived owner keep their ``x`` bit-exactly (§12)."""
    n = xl.shape[0]
    D = x_bar.shape[0]
    bar = x_bar.astype(xl.dtype)[None]
    if covered is None:
        if down is None:
            return jnp.broadcast_to(bar, (n, D)).reshape(xl.shape)
        return jnp.where(
            down[:, None], bar, xl.reshape(n, D)
        ).reshape(xl.shape)
    dm = (jnp.ones((n, 1), bool) if down is None else down[:, None])
    return jnp.where(
        dm & covered[None, :], bar, xl.reshape(n, D)
    ).reshape(xl.shape)


def _finish_leaf(xl, hl, xf, x_bar, owned, scale, down=None, covered=None):
    """The fused h-update + DownCom shared by both uplinks: reads x, h
    once, writes h_new and x_new — ownership is the branch-free predicate
    evaluated inside the fusion, ``down`` the DownCom row mask,
    ``covered`` the survivor-aware per-coordinate DownCom gate (the
    h-update needs no gate: an uncovered coordinate has no arrived owner,
    so ``owned`` is already false on every row there)."""
    n = xl.shape[0]
    D = xf.shape[1]
    with jax.named_scope(spans.UPLINK):
        h_new = hl.reshape(n, D).astype(jnp.float32) + scale * jnp.where(
            owned, x_bar[None] - xf, 0.0
        )
    return (
        _downcom(xl, x_bar, down, covered),
        h_new.astype(hl.dtype).reshape(hl.shape),
    )


def _survivor_bar(num, cnt):
    """``x_bar = num / max(cnt, 1)`` + the covered mask: the per-
    coordinate 1/(arrived owner count) rebuild.  Under zero drops
    ``cnt == s`` everywhere, so the division is bit-identical to the
    static ``num / s``."""
    return num / jnp.maximum(cnt, 1.0), cnt > 0


def _pallas_comm(xw, hw, slot, band, m: int, s: int, scale, block: int,
                 down=None, survivor=False, wire_x=None, wire_scales=None,
                 wire_chunk=None, xbar_tx=None, robust=None):
    from repro.kernels import compress as _compress
    from repro.kernels import uplink  # lazy: keep dist importable w/o pallas

    # wire lanes: int codes expand through the shared dequant before the
    # kernels (robust stats run on DEQUANTIZED values, the §13 rule);
    # narrow float lanes cast per tile — either way the accumulation (and
    # the psum shape upstream) stays f32
    if wire_scales is not None:
        xin = _compress.wire_dequant(wire_x, wire_scales, wire_chunk)
    else:
        xin = xw if wire_x is None else wire_x

    def _msum(counts):
        return uplink.masked_sum(
            xin, slot, band, m, s, counts=counts, block=block
        )

    if robust is not None:
        x_bar, rcnt = uplink.robust_sum(
            xin.astype(jnp.float32), slot, band, m, s, kind=robust[0],
            k=robust[1], block=block,
        )
        covered = (rcnt > 0) if survivor else None
    elif survivor:
        num, cnt = _msum(True)
        # survivor rebuild AFTER dequantization: PR 6 semantics unchanged
        x_bar, covered = _survivor_bar(num, cnt)
    else:
        x_bar, covered = _msum(False), None
    if xbar_tx is not None:
        x_bar = xbar_tx(x_bar)
    h_new, x_new = uplink.h_update(
        xw, hw, x_bar, slot, band, m, s, float(scale), down=down,
        covered=covered, block=block,
    )
    return x_bar, h_new, x_new


# --------------------------------------------------------------------------
# the shard-resident engine (impl="pallas", meshed=True — DESIGN.md §10)
# --------------------------------------------------------------------------


def _use_shard_kernels(flag: Optional[bool]) -> bool:
    """None -> Pallas kernels per shard on TPU, fused-jnp sparse gathers
    elsewhere (interpret-mode kernels unroll the grid on CPU — a
    correctness path the tests force, not the production one)."""
    if flag is None:
        return jax.default_backend() == "tpu"
    return bool(flag)


def _leaf_trail_specs(xflat: Sequence[jax.Array], pspecs) -> List[tuple]:
    """Per-leaf trailing-dim PartitionSpec entries (client entry dropped,
    right-padded with None to the leaf rank).  ``pspecs=None`` means only
    the client axis is split (generic stacked trees)."""
    from jax.sharding import PartitionSpec as P

    if pspecs is None:
        return [(None,) * (a.ndim - 1) for a in xflat]
    specs = jax.tree.leaves(pspecs, is_leaf=lambda sp: isinstance(sp, P))
    out = []
    for a, sp in zip(xflat, specs):
        tr = tuple(sp)[1:]
        out.append(tr + (None,) * (a.ndim - 1 - len(tr)))
    return out


def _shard_coords(local_trail: tuple, global_trail: tuple, entries: tuple,
                  mesh):
    """Global flat coordinate index ((d_local,) int32, row-major over the
    GLOBAL trailing dims) of the executing shard's block of one leaf —
    or None when the block IS the whole leaf (static tables apply).  The
    per-dim offsets come from the mesh axis indices of the dims'
    PartitionSpec entries, so model-parallel leaves get the right bands.
    Only valid inside ``shard_map``."""
    from repro.dist import sharding as _shr

    if tuple(local_trail) == tuple(global_trail):
        return None
    strides, acc = [], 1
    for g in reversed(global_trail):
        strides.append(acc)
        acc *= int(g)
    strides.reverse()
    k = None
    for d, (loc, st, entry) in enumerate(
            zip(local_trail, strides, entries)):
        off = jnp.int32(0)
        for name in _shr.spec_dim_axes(entry):
            off = off * mesh.shape[name] + jax.lax.axis_index(name)
        idx = (jax.lax.iota(jnp.int32, loc) + off * loc) * jnp.int32(st)
        shape = [1] * len(local_trail)
        shape[d] = loc
        idx = idx.reshape(shape)
        k = idx if k is None else k + idx
    return jnp.broadcast_to(k, tuple(local_trail)).reshape(-1)


def _leaf_comm(xl, hl, slot, m: int, s: int, scale, template: str, down,
               survivor: bool, psum):
    """One leaf through the leaf-native kernels, in its own layout: the
    local rows' UpCom partial (``leaf_masked_sum``), ``psum`` over the
    client shards, the rebuild, and the in-place h-update + DownCom
    (``leaf_h_update``).  No band table and no packed copy."""
    from repro.kernels import uplink

    rows = xl.shape[0]
    R, C = uplink.leaf_view(xl.shape[1:])
    x3, h3 = xl.reshape(rows, R, C), hl.reshape(rows, R, C)
    if survivor:
        num, cnt = uplink.leaf_masked_sum(x3, slot, m, s, template=template,
                                          counts=True)
        x_bar, cov = _survivor_bar(psum(num), psum(cnt))
    else:
        x_bar = psum(uplink.leaf_masked_sum(x3, slot, m, s,
                                            template=template))
        cov = None
    h_new, x_new = uplink.leaf_h_update(
        x3, h3, x_bar, slot, m, s, float(scale), template=template,
        down=down, covered=cov)
    return x_new.reshape(xl.shape), h_new.reshape(hl.shape)


# Leaves below this many coordinates a row (norm scales, biases) take the
# per-leaf jnp route even where the kernels are on: a kernel launch there
# costs more than the leaf.
LEAF_MIN = 1 << 16
ROUTES = ("leaf", "workspace", "jnp")


def _leaf_routes(dims: Sequence[int], split: Sequence[bool], template: str,
                 m: int, s: int, *, kernels: bool, wirep: Optional[str],
                 robust, dp_total: int) -> List[str]:
    """Each leaf's route through the shard engine, from what the call
    shows: ``"leaf"`` (the leaf-native kernels, in the leaf's own
    layout), ``"workspace"`` (packed with the other leaves of its wire
    kind) or ``"jnp"`` (fused jnp per leaf).  With the kernels on, an
    f32-wire, mean-combined leaf that is not split over model axes takes
    the leaf kernels (the jnp route below ``LEAF_MIN`` coordinates); the
    quantized wire (its per-chunk scales lie over the packed flat axis),
    robust combiners and model-sharded leaves (bands from the global
    coordinate index) keep the workspace.  Tall cyclic leaves, robust
    leaves across client shards (the kernels psum a PARTIAL sum, but an
    order statistic needs the whole owner stack on every shard; one
    client shard holds every owner row, so there the robust kernel
    runs), and every leaf with the kernels off take the jnp route."""
    out = []
    for D, sp in zip(dims, split):
        tall = template == "cyclic" and D * s < m
        if not kernels or tall or (robust is not None and dp_total > 1):
            out.append("jnp")
        elif wirep is not None or robust is not None or sp:
            out.append("workspace")
        else:
            out.append("leaf" if D >= LEAF_MIN else "jnp")
    return out


def _model_split(trail: Sequence[tuple], mesh) -> List[bool]:
    """Per leaf: is any trailing dim split over mesh axes of size > 1
    (its shard_map block then is not the whole leaf)."""
    from repro.dist import sharding as _shr

    def factor(entry):
        return int(np.prod([mesh.shape[a]
                            for a in _shr.spec_dim_axes(entry)] or [1]))

    return [any(factor(e) > 1 for e in tr) for tr in trail]


def route_coords(x: Any, template: str, m: int, s: int, *,
                 impl: Optional[str], mesh, pspecs=None,
                 wire: Optional[str] = None, robust=None,
                 shard_kernels: Optional[bool] = None) -> dict:
    """Coordinates of one client row each route of the meshed comm step
    takes (``ROUTES``; ``_leaf_routes``), for stacked state ``x`` (arrays
    or shape structs): what ``make_comm_step`` reports as
    ``fn.route_coords``.  An impl other than the shard engine runs every
    leaf through jnp."""
    from repro.dist import sharding as _shr

    xflat = jax.tree.leaves(x)
    dims = [int(np.prod(a.shape[1:])) for a in xflat]
    out = dict.fromkeys(ROUTES, 0)
    if effective_impl(impl, meshed=True, mesh=mesh) != "pallas":
        out["jnp"] = sum(dims)
        return out
    dp_total = int(np.prod([mesh.shape[a]
                            for a in _shr.dp_axis_names(mesh)] or [1]))
    routes = _leaf_routes(
        dims, _model_split(_leaf_trail_specs(xflat, pspecs), mesh),
        template, m, s, kernels=_use_shard_kernels(shard_kernels),
        wirep=_wire_policy(wire), robust=robust, dp_total=dp_total)
    for D, r in zip(dims, routes):
        out[r] += D
    return out


def _shard_comm(
    x: Any,
    h: Any,
    slot: jax.Array,  # (n,) int32 owner column per client; -1 = idle
    m: int,  # template modulus: c (the cohort size; == n at full PP)
    s: int,
    scale,
    *,
    template: str,  # "cyclic" | "blocked"
    mesh,
    pspecs,  # pytree of PartitionSpec matching x (None: client split only)
    block: int,
    use_kernels: Optional[bool],
    down: Optional[jax.Array] = None,  # (n,) DownCom rows; None = all
    faulted: bool = False,  # an arrival mask was applied to ``slot``
    survivor: bool = False,  # per-coordinate arrived-owner-count rebuild
    wire: Optional[str] = None,  # wire policy; None/"f32" = f32 lanes
    wire_seed=None,  # uint32 round seed for the stochastic draws
    wire_down: bool = False,  # quantize the DownCom broadcast too
    robust: Optional[Tuple[str, int]] = None,  # normalized robust spec
) -> Tuple[Any, Any]:
    """The shard-resident comm step: one ``shard_map`` over the dp axes.

    Per shard: UpCom partials over the LOCAL client rows only, per leaf
    route (``_leaf_routes``): the leaf-native kernels in each leaf's own
    layout (``_leaf_comm``), Pallas ``masked_sum`` on a per-shard
    workspace, or fused jnp (coarse whole-chunk gathers for the blocked
    template's contiguous ownership, masked local-row sums for the cyclic
    one — see ``local_partial`` for the measured why) — then the shards
    combine with d-sized ``psum``s of the ``1/s``-folded partials (one
    per workspace; per leaf on the other routes — measured, see the body
    comment), and the h-update + the DownCom broadcast run per shard on
    local rows.  No ``(n, d)``-sized collective appears at any point
    (HLO-regression-tested); the client axis is padded to the dp extent
    with idle rows when it does not divide.

    ``robust`` (a normalized ``robust.normalize_robust`` spec) switches
    the UpCom from the 1/s (or survivor) partial-sum rebuild to a
    per-coordinate robust combine.  Order statistics do not decompose
    over shards, so the partial-sum psum is replaced by an
    ``(s, d_local)``-bounded owner-value exchange: each shard gathers
    the owner rows it hosts into the stack (zeros elsewhere), ONE psum
    of the stack assembles all ``s`` owner values per coordinate on
    every shard — bounded by ``s``, never ``(n, d)`` — and the combine
    runs in jnp per shard (kernel grouping is disabled for robust
    leaves across client shards; the HLO regression test pins the
    collective bound).  On a single client shard every owner row is
    local: the robust kernel path of the unsharded engine runs there."""
    from jax.sharding import PartitionSpec as P
    from repro.dist import sharding as _shr

    xflat, treedef = jax.tree.flatten(x)
    hflat = jax.tree.leaves(h)
    n = int(xflat[0].shape[0])
    dp_names = _shr.dp_axis_names(mesh)
    dp = _shr.dp_axes(mesh)
    dp_total = int(np.prod([mesh.shape[a] for a in dp_names] or [1]))
    kernels = _use_shard_kernels(use_kernels)
    trail = _leaf_trail_specs(xflat, pspecs)

    # column -> owner client row, built on the GLOBAL slot and replicated
    # into every shard (tiny).  Cyclic: every template column in [0, c)
    # has exactly one cohort owner.  Blocked: slot is a permutation of
    # [0, c) over the COHORT rows (idle rows -1), and the owner of block
    # j at shift t is the client whose slot equals (t - j) mod c.
    client_of = (
        jnp.zeros((m + 1,), jnp.int32)
        .at[jnp.where(slot >= 0, slot, m)]
        .set(jnp.arange(n, dtype=jnp.int32))[:m]
    )
    # under faults a dropped owner's column has NO live row, but
    # client_of defaults it to row 0 — col_ok marks the live columns so
    # the coarse per-block gathers can gate the phantom contribution
    # (the predicate-based paths need no gate: slot -1 owns nothing)
    col_ok = None
    if faulted:
        col_ok = (
            jnp.zeros((m + 1,), bool)
            .at[jnp.where(slot >= 0, slot, m)]
            .set(True)[:m]
        )

    # pad the client axis to the dp extent: padded rows are idle (slot -1,
    # zero state) — never owners, never owned — and sliced off after.
    # jnp.pad, NOT jnp.concatenate: GSPMD reshards a concat feeding a
    # shard_map through dynamic-update-slices and an extra all-reduce over
    # the mesh (jax 0.9 on a 4x2 CPU mesh: 2 all-reduces where pad lowers
    # to 1; an older JAX summed the blocks once per model replica there,
    # double-counting the state).
    pad = (-n) % dp_total
    dwn = (jnp.ones((n,), bool) if down is None
           else jnp.asarray(down).astype(bool))
    if pad:
        xflat = [
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            for a in xflat
        ]
        hflat = [
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            for a in hflat
        ]
        slot = jnp.pad(slot, (0, pad), constant_values=-1)
        dwn = jnp.pad(dwn, (0, pad), constant_values=False)
    rows = (n + pad) // dp_total

    # global trailing dims per leaf (the inputs to shard_map are global;
    # inside the body the blocks are these divided by the split factors)
    gtrail = [tuple(int(d) for d in a.shape[1:]) for a in xflat]
    gD = [int(np.prod(g)) if g else 1 for g in gtrail]
    tall = [template == "cyclic" and D * s < m for D in gD]

    # the wire policy resolves on the GLOBAL leaf dims — the same kinds
    # every unsharded impl resolves, so quantized values agree bitwise
    wirep = _wire_policy(wire)
    wseed = _wire_seed(wire_seed) if wirep is not None else None
    wdown = bool(wire_down) and wirep is not None
    kinds = [
        _wire.resolve_kind(D, wirep) if wirep is not None else "f32"
        for D in gD
    ]
    routes = _leaf_routes(gD, _model_split(trail, mesh), template, m, s,
                          kernels=kernels, wirep=wirep, robust=robust,
                          dp_total=dp_total)

    def _leaf_axes(i):
        names = []
        for entry in trail[i]:
            names.extend(_shr.spec_dim_axes(entry))
        return tuple(names)

    leaf_specs = tuple(P(dp, *tr) for tr in trail)

    def _leaf_band(i, k_arr):
        """Per-coordinate kernel-convention band of leaf i's shard block:
        static np table when the block is the whole leaf, recomputed from
        the global coordinate index when model-sharded.  Shared by the
        jnp ownership predicate AND the kernel operands — the single
        source of the band formula per template."""
        D = gD[i]
        if template == "blocked":
            if k_arr is None:
                return jnp.asarray(_block_leaf_band_np(D, m))
            return k_arr // (-(-D // m))
        if k_arr is None:
            return jnp.asarray(_cyclic_band_np((D,), m, s))
        return (-(s * (k_arr % m))) % m

    def _owned(i, k_arr, sl2):
        """Local-row ownership predicate (n_loc, d_loc), branch-free: two
        compares against the leaf band.  NOTE the off-mesh ws path's
        repeat-expanded block predicate is NOT used here: ``jnp.repeat``
        inside shard_map lowers pathologically on CPU (measured ~10x the
        whole comm step; the band-compare form is flat)."""
        D = gD[i]
        if tall[i]:
            kk = (jnp.asarray(np.arange(D, dtype=np.int32))
                  if k_arr is None else k_arr)
            return (sl2 >= 0) & (sl2 < D * s) & (sl2 % D == kk[None, :])
        return _wrapped_owned(sl2, _leaf_band(i, k_arr)[None, :], m, s)

    def body(xs, hs, sl, cof, *rest):
        cok, dw = rest if faulted else (None, rest[0])
        row0 = _shr.dp_shard_index(mesh) * rows
        sl2 = sl[:, None]
        coords = [
            _shard_coords(tuple(a.shape[1:]), gtrail[i], trail[i], mesh)
            for i, a in enumerate(xs)
        ]
        xfs = [a.reshape(rows, -1).astype(jnp.float32) for a in xs]
        # quantized UpCom payloads (local rows, global row ids/coords —
        # bitwise the unsharded impls' rows).  Unused entries (f32 leaves,
        # kernel-covered leaves packing their own codes) are dead code XLA
        # drops; h/DownCom keep reading the raw xfs.
        xqs = list(xfs)
        if wirep is not None:
            for i in range(len(xs)):
                q = _leaf_quant(
                    kinds[i], wseed, i, gD[i], row0=row0,
                    coords=coords[i], axes=_leaf_axes(i),
                )
                if q is not None:
                    xqs[i] = q(xfs[i])

        def local_partial(i, counts=False):
            """This shard's UpCom partial, 1/s folded in (``counts=True``,
            the survivor path: raw sum + per-coordinate count of LOCALLY
            resident arrived owners — each owner lives on exactly one
            shard, so the psum'd counts are the global arrived-owner
            counts).

            Blocked template on an unsharded leaf with more local rows
            than shifts: ownership contiguity means block j's owners at
            the s shifts are whole-chunk reads, so the partial is s
            coarse (block, chunk) gathers over the LOCAL rows — O(s d)
            reads vs the masked form's O(rows d), a measured 2x at
            n=32 on the host mesh (at rows < s the masked form reads
            less and wins, so the gate is static).  Everything else:
            masked local-row sum with the fused ownership predicate —
            per-element row-gathers lower pathologically inside shard_map
            on CPU (measured 12x slower than the same gather outside),
            and per shard the row count is tiny, so the masked form IS
            the cheap one; on TPU the Pallas kernels cover these leaves
            instead.
            """
            xf = xqs[i]  # the wire payload (== xfs[i] on the f32 path)
            if (template == "blocked" and coords[i] is None
                    and rows >= s):
                D = gD[i]
                chunk = -(-D // m)
                nf, tailn = divmod(D, chunk)
                xm = xf[:, :nf * chunk].reshape(rows, nf, chunk)
                jf = np.arange(nf, dtype=np.int32)
                accm = jnp.zeros((nf, chunk), jnp.float32)
                acct = jnp.zeros((tailn,), jnp.float32)
                cntm = jnp.zeros((nf,), jnp.float32)
                cntt = jnp.zeros((), jnp.float32)
                for t in range(s):
                    # owner of block j at shift t: the client whose slot
                    # is (t - j) mod n — local rows contribute, the rest
                    # land on their own shards
                    own = cof[jnp.asarray((t - jf) % m)]
                    loc = (own >= row0) & (own < row0 + rows)
                    if cok is not None:
                        loc = loc & cok[jnp.asarray((t - jf) % m)]
                    rr = jnp.clip(own - row0, 0, rows - 1)
                    accm = accm + jnp.where(loc[:, None], xm[rr, jf], 0.0)
                    if counts:
                        cntm = cntm + loc.astype(jnp.float32)
                    if tailn:
                        ot = cof[(t - nf) % m]
                        lt = (ot >= row0) & (ot < row0 + rows)
                        if cok is not None:
                            lt = lt & cok[(t - nf) % m]
                        rt = jnp.clip(ot - row0, 0, rows - 1)
                        acct = acct + jnp.where(lt, xf[rt, nf * chunk:], 0.0)
                        if counts:
                            cntt = cntt + lt.astype(jnp.float32)
                flat = (jnp.concatenate([accm.reshape(-1), acct])
                        if tailn else accm.reshape(-1))
                if counts:
                    cnt = jnp.repeat(cntm, chunk)
                    cnt = (jnp.concatenate(
                        [cnt, jnp.broadcast_to(cntt, (tailn,))])
                        if tailn else cnt)
                    return flat, cnt
                return flat / s
            # predicate recomputed here AND in the finish (not cached):
            # sharing it across the psum boundary forces XLA to
            # materialize a (rows, d) pred buffer; recomputed, it stays
            # two compares inside each fusion (what the ws path does)
            owned_loc = _owned(i, coords[i], sl2)
            num = jnp.where(owned_loc, xf, 0.0).sum(axis=0)
            if counts:
                return num, owned_loc.astype(jnp.float32).sum(axis=0)
            return num / s

        def _psum(v):
            return jax.lax.psum(v, dp_names) if dp_names else v

        # Per-shard UpCom partials -> d-sized psums.  The kernel path's
        # partial is the packed workspace's masked_sum output — already
        # one flat vector, ONE psum.  The jnp leaves psum per leaf:
        # concatenating them into a single flat psum measured ~5x slower
        # on CPU (the concat write + per-leaf slice reads break XLA's
        # leafwise fusion); per-leaf psums keep each leaf's partial,
        # combine, and finish in one fused pipeline, and XLA's collective
        # combiner can still merge the all-reduces on real backends.
        out_x: List[Any] = [None] * len(xs)
        out_h: List[Any] = [None] * len(xs)
        covered = [i for i, r in enumerate(routes) if r == "workspace"]
        rest = [i for i, r in enumerate(routes) if r == "jnp"]
        for i in (i for i, r in enumerate(routes) if r == "leaf"):
            out_x[i], out_h[i] = _leaf_comm(
                xs[i], hs[i], sl, m, s, scale, template, dw, survivor,
                _psum)
        if covered:
            from repro.kernels import compress as _compress
            from repro.kernels import uplink

            # one workspace (and one d-sized psum) per wire kind: the f32
            # path is a single group taking the PR 6 code verbatim; under
            # "auto" at most two (f16 + int8)
            if wirep is None:
                groups = [(None, covered)]
            else:
                gmap: dict = {}
                for i in covered:
                    gmap.setdefault(kinds[i], []).append(i)
                groups = sorted(gmap.items())
            for gkind, idxs in groups:
                gdims = [gD[i] for i in idxs]
                spec = workspace_spec([xs[i] for i in idxs],
                                      rows_total=n + pad, wire=wirep,
                                      wire_dims=gdims)
                hspec = workspace_spec([hs[i] for i in idxs],
                                       rows_total=n + pad)
                xw = pack([xs[i] for i in idxs], spec)
                hw = pack([hs[i] for i in idxs], hspec)
                band_parts = [_leaf_band(i, coords[i]) for i in idxs]
                band_ws = (band_parts[0] if len(band_parts) == 1
                           else jnp.concatenate(band_parts))
                wx = wsc = wcc = tx = None
                if gkind is not None:
                    flats = [xw[:, o:o + D]
                             for o, D in zip(spec.offsets, spec.dims)]
                    wx, wsc, wcc = _wire_pack(
                        flats, idxs, gdims, gkind, wseed, row0=row0,
                        coords=[coords[i] for i in idxs],
                        axes=[_leaf_axes(i) for i in idxs],
                    )
                if wdown:
                    tx = _make_xbar_tx(
                        spec.offsets, spec.dims, gdims, idxs, kinds,
                        wseed, coords=[coords[i] for i in idxs],
                        axes=[_leaf_axes(i) for i in idxs],
                    )

                def _msum(counts, _xw=xw, _wx=wx, _wsc=wsc, _wcc=wcc,
                          _band=band_ws):
                    if _wsc is not None:
                        xin = _compress.wire_dequant(_wx, _wsc, _wcc)
                    else:
                        xin = _xw if _wx is None else _wx
                    return uplink.masked_sum(
                        xin, sl, _band, m, s, counts=counts, block=block
                    )

                if robust is not None:  # one shard: no psum needed
                    _, h_new_ws, x_new_ws = _pallas_comm(
                        xw, hw, sl, band_ws, m, s, scale, block, down=dw,
                        survivor=survivor, wire_x=wx, wire_scales=wsc,
                        wire_chunk=wcc, xbar_tx=tx, robust=robust,
                    )
                elif survivor:
                    num_ws, cnt_ws = _msum(True)
                    xbar_ws, cov_ws = _survivor_bar(
                        _psum(num_ws), _psum(cnt_ws)
                    )
                    if tx is not None:
                        xbar_ws = tx(xbar_ws)
                    h_new_ws, x_new_ws = uplink.h_update(
                        xw, hw, xbar_ws, sl, band_ws, m, s, float(scale),
                        down=dw, covered=cov_ws, block=block,
                    )
                else:
                    xbar_ws = _psum(_msum(False))
                    if tx is not None:
                        xbar_ws = tx(xbar_ws)
                    h_new_ws, x_new_ws = uplink.h_update(
                        xw, hw, xbar_ws, sl, band_ws, m, s, float(scale),
                        down=dw, block=block,
                    )
                xs_un = unpack(x_new_ws, spec)
                hs_un = unpack(h_new_ws, hspec)
                for j, i in enumerate(idxs):
                    out_x[i], out_h[i] = xs_un[j], hs_un[j]
        for i in rest:
            with jax.named_scope(spans.UPLINK):
                if robust is not None:
                    # the (s, d_local)-bounded owner-value exchange: owner
                    # columns derive from the band ((t - band) mod m owns
                    # coordinate k at shift t — the inverse of the shared
                    # (slot + band) mod m < s predicate), each shard fills
                    # the stack rows whose owner it hosts, and ONE psum of
                    # the (s, d_local) stack replicates all owner values —
                    # never an (n, d)-sized collective
                    xf = xqs[i]
                    if tall[i]:
                        kk = (jnp.asarray(np.arange(gD[i], dtype=np.int32))
                              if coords[i] is None else coords[i])
                        colz = jnp.stack(
                            [kk + t * gD[i] for t in range(s)])
                    else:
                        bd = _leaf_band(i, coords[i])
                        colz = jnp.stack([(t - bd) % m for t in range(s)])
                    own = cof[colz]  # (s, d_local) global owner row
                    okm = (jnp.ones(colz.shape, bool) if cok is None
                           else cok[colz])
                    loc = (own >= row0) & (own < row0 + rows) & okm
                    rr = jnp.clip(own - row0, 0, rows - 1)
                    stack = jnp.where(
                        loc, jnp.take_along_axis(xf, rr, axis=0), 0.0)
                    stack = _psum(stack)
                    x_bar, rcnt = _robust.robust_combine_stack(
                        stack, okm, *robust)
                    cov = (rcnt > 0) if survivor else None
                elif survivor:
                    num, cnt = local_partial(i, counts=True)
                    x_bar, cov = _survivor_bar(_psum(num), _psum(cnt))
                else:
                    x_bar, cov = _psum(local_partial(i)), None
            if wdown:
                x_bar = _down_quant(
                    kinds[i], wseed, i, gD[i], coords[i], _leaf_axes(i)
                )(x_bar)
            out_x[i], out_h[i] = _finish_leaf(
                xs[i], hs[i], xfs[i], x_bar, _owned(i, coords[i], sl2),
                scale, dw, cov,
            )
        return tuple(out_x), tuple(out_h)

    if faulted:
        in_specs = (leaf_specs, leaf_specs, P(dp), P(), P(), P(dp))
        operands = (tuple(xflat), tuple(hflat), slot, client_of, col_ok,
                    dwn)
    else:
        in_specs = (leaf_specs, leaf_specs, P(dp), P(), P(dp))
        operands = (tuple(xflat), tuple(hflat), slot, client_of, dwn)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(leaf_specs, leaf_specs),
        check_vma=False,
    )
    xs_out, hs_out = fn(*operands)
    if pad:
        xs_out = [a[:n] for a in xs_out]
        hs_out = [a[:n] for a in hs_out]
    return (
        jax.tree.unflatten(treedef, list(xs_out)),
        jax.tree.unflatten(treedef, list(hs_out)),
    )


def cyclic_comm(
    x: Any,
    h: Any,
    slot: jax.Array,  # (n,) int32 template column per client; -1 = idle
    c: int,
    s: int,
    scale,
    impl: str = "ws",
    *,
    down: Optional[jax.Array] = None,
    arrived: Optional[jax.Array] = None,
    correct: bool = True,
    block: int = 4096,
    meshed: bool = False,
    mesh=None,
    pspecs=None,
    shard_kernels: Optional[bool] = None,
    wire: Optional[str] = None,
    wire_seed=None,
    wire_down: bool = False,
    robust: Optional[Tuple[str, int]] = None,
) -> Tuple[Any, Any]:
    """masked_psum UpCom + h-update + DownCom for the cyclic template.

    Coordinate-identical to the per-leaf dense reference (``impl="dense"``)
    for every leaf and both Fig. 1 template regimes; see the module
    docstring for the three implementations.  ``down`` is the DownCom row
    mask ((n,) bool; None broadcasts to every row) — the elastic engine
    passes the NEXT round's cohort so idle rows stay untouched (§11).
    ``arrived``/``correct`` are the fault-tolerant aggregation inputs
    (§12, module docstring): rows outside ``arrived`` are demoted to idle
    and, with ``correct=True``, the rebuild divides by the per-coordinate
    arrived-owner count with uncovered coordinates left untouched.
    ``meshed=True`` with a ``mesh`` handle and ``impl="pallas"`` runs the
    shard-resident engine (``pspecs``: the stacked state's PartitionSpecs,
    client split only when None; ``shard_kernels``: force/suppress the
    per-shard Pallas kernels, default per backend).

    ``wire`` narrows the UpCom payload per the §13 wire format (policy
    from ``repro.dist.wire``; ``None``/``"f32"`` take the PR 6 code paths
    verbatim), ``wire_seed`` is the round's uint32 quantization seed
    (``wire.round_seed``), and ``wire_down`` additionally quantizes the
    DownCom broadcast.  All four impls quantize the same (row, coord)
    payload with the same counter-hash draw, so they agree to float-sum
    reordering exactly as on the f32 path.

    ``robust`` replaces the arrived-owner mean with a per-coordinate
    robust combine over the owner-value stack (DESIGN.md §15): pass the
    normalized ``robust.normalize_robust(kind, k, s)`` spec — ``None``
    (mean, or trimmed with k=0) runs the existing paths verbatim,
    bitwise.  Robust stats are computed on DEQUANTIZED wire values and
    compose with ``arrived``/``correct`` (uncovered coordinates still
    pass through untouched) and ``down``.
    """
    impl = effective_impl(impl, meshed=meshed, mesh=mesh)
    faulted = arrived is not None
    survivor = faulted and correct
    if faulted:
        slot = jnp.where(
            jnp.asarray(arrived).astype(bool), slot, -1
        ).astype(jnp.int32)
    if impl == "pallas" and meshed:
        return _shard_comm(
            x, h, slot, c, s, scale, template="cyclic", mesh=mesh,
            pspecs=pspecs, block=block, use_kernels=shard_kernels,
            down=down, faulted=faulted, survivor=survivor,
            wire=wire, wire_seed=wire_seed, wire_down=wire_down,
            robust=robust,
        )
    xflat, treedef = jax.tree.flatten(x)
    hflat = jax.tree.leaves(h)
    dims = [int(np.prod(a.shape[1:])) for a in xflat]
    n = xflat[0].shape[0] if xflat else 0
    out_x: List[Any] = [None] * len(xflat)
    out_h: List[Any] = [None] * len(xflat)
    wirep = _wire_policy(wire)
    wseed = _wire_seed(wire_seed) if wirep is not None else None
    wdown = bool(wire_down) and wirep is not None
    kinds = [_wire.resolve_kind(D, wirep) if wirep is not None else "f32"
             for D in dims]

    if impl == "ws":
        client_of = None
        col_ok = None
        if not meshed or robust is not None:
            # column -> client row of this round (idle writes land in the
            # dropped overflow slot; every column has exactly one owner).
            # Robust combines need the owner-value STACK even when the
            # client axis is meshed: the psum-shaped partial sum cannot
            # express an order statistic, so the gather form applies
            # (GSPMD pays gather collectives here; the HLO-gated meshed
            # placement is the shard engine, not this path).
            client_of = (
                jnp.zeros((c + 1,), jnp.int32)
                .at[jnp.where(slot >= 0, slot, c)]
                .set(jnp.arange(n, dtype=jnp.int32))[:c]
            )
            if faulted:
                # columns whose owner dropped default to row 0 in
                # client_of — col_ok gates those phantom gathers
                col_ok = (
                    jnp.zeros((c + 1,), bool)
                    .at[jnp.where(slot >= 0, slot, c)]
                    .set(True)[:c]
                )
        sl = slot[:, None]
        for i, (xl, hl) in enumerate(zip(xflat, hflat)):
            D = dims[i]
            cols, band, tall = _cyclic_leaf_tables_np(D, c, s)
            xf = xl.reshape(n, D).astype(jnp.float32)
            quant = _leaf_quant(kinds[i], wseed, i, D)
            # UpCom reads the wire payload; the h-update below reads the
            # raw rows (core/tamuna.py quantizes the numerator only).
            # Masking is where-select, so quantizing unsanitized idle rows
            # is safe — an owner row's payload is identical in every impl.
            xq = xf if quant is None else quant(xf)
            if tall:
                kj = jnp.arange(D, dtype=jnp.int32)[None, :]
                owned = (sl < D * s) & (sl % D == kj)
            else:
                owned = _wrapped_lt(sl - jnp.asarray(band)[None, :], c, s)
            owned = owned & (sl >= 0)
            with jax.named_scope(spans.UPLINK):
                if robust is not None:
                    if not faulted and not tall:
                        # gather-free owner stack: the cyclic owner column
                        # (s k + t) mod c only depends on k mod c, so stack
                        # row t is a constant-mask select chain over the
                        # slot-ordered rows xq[client_of] — all elementwise,
                        # so the whole combine stays one parallelizable
                        # fusion (an elementwise consumer of the (s, D)
                        # take_along_axis form drags the per-element gather
                        # into a serial loop body and costs ~3x the mean
                        # step at production widths)
                        xs = xq[client_of]  # (c, D) row permutation
                        resid = np.arange(D, dtype=np.int64) % c
                        masks = [resid == r for r in range(c)]
                        stack = []
                        for t in range(s):
                            y = xs[(s * (c - 1) + t) % c]
                            for r in range(c - 2, -1, -1):
                                y = jnp.where(
                                    jnp.asarray(masks[r]),
                                    xs[(s * r + t) % c], y)
                            stack.append(y)
                        vals = jnp.stack(stack)
                        ok = None
                    else:
                        # robust combine over the (s, D) owner-row gather
                        # stack (same gathers the mean path reads; tall
                        # leaves use their explicit owner-column table)
                        rows = client_of[jnp.asarray(cols)]
                        vals = jnp.take_along_axis(xq, rows, axis=0)
                        ok = col_ok[jnp.asarray(cols)] if faulted else None
                    x_bar, rcnt = _robust.robust_combine_stack(
                        vals, ok, *robust)
                    cov = (rcnt > 0) if survivor else None
                elif meshed:
                    # client axis sharded across devices: the owner rows live
                    # on other shards, so a gather would all-gather (n, D) --
                    # keep the psum shape (a d-sized all-reduce, the minimum)
                    # with the predicate fused into the local partial sum
                    num = jnp.where(owned, xq, 0.0).sum(axis=0)
                    if survivor:
                        x_bar, cov = _survivor_bar(
                            num, owned.astype(jnp.float32).sum(axis=0)
                        )
                    else:
                        x_bar, cov = num / s, None
                else:
                    # sparse UpCom: s row-gathers + 1/s rebuild, O(s D) reads
                    rows = client_of[jnp.asarray(cols)]  # (s, D) owner rows
                    vals = jnp.take_along_axis(xq, rows, axis=0)
                    if faulted:
                        ok = col_ok[jnp.asarray(cols)]  # (s, D) owner arrived
                        num = jnp.where(ok, vals, 0.0).sum(axis=0)
                        if survivor:
                            x_bar, cov = _survivor_bar(
                                num, ok.astype(jnp.float32).sum(axis=0)
                            )
                        else:
                            x_bar, cov = num / s, None
                    else:
                        x_bar, cov = vals.sum(axis=0) / s, None
            if wdown:
                x_bar = _down_quant(kinds[i], wseed, i, D)(x_bar)
            out_x[i], out_h[i] = _finish_leaf(
                xl, hl, xf, x_bar, owned, scale, down, cov
            )
        return (
            jax.tree.unflatten(treedef, out_x),
            jax.tree.unflatten(treedef, out_h),
        )

    if impl == "dense":
        covered: List[int] = []
    else:  # pallas: tall-regime leaves keep the dense closed form
        covered = [i for i, D in enumerate(dims) if D * s >= c]
    fallback = [i for i in range(len(xflat)) if i not in covered]

    for i in fallback:
        out_x[i], out_h[i] = _dense_cyclic_leaf(
            xflat[i], hflat[i], slot, c, s, scale, down,
            sanitize=faulted, survivor=survivor,
            quant=_leaf_quant(kinds[i], wseed, i, dims[i]),
            down_quant=(_down_quant(kinds[i], wseed, i, dims[i])
                        if wdown else None),
            robust=robust,
        )

    if covered:
        # one workspace per wire kind (see _shard_comm): the f32 path is
        # the single group (None, covered) running the PR 6 code verbatim
        if wirep is None:
            groups = [(None, covered)]
        else:
            gmap: dict = {}
            for i in covered:
                gmap.setdefault(kinds[i], []).append(i)
            groups = sorted(gmap.items())
        for gkind, idxs in groups:
            spec = workspace_spec([xflat[i] for i in idxs], wire=wirep)
            hspec = workspace_spec([hflat[i] for i in idxs])
            xw = pack([xflat[i] for i in idxs], spec)
            hw = pack([hflat[i] for i in idxs], hspec)
            band = jnp.asarray(_cyclic_band_np(spec.dims, c, s))
            wx = wsc = wcc = tx = None
            if gkind is not None:
                flats = [xw[:, o:o + D]
                         for o, D in zip(spec.offsets, spec.dims)]
                wx, wsc, wcc = _wire_pack(
                    flats, idxs, list(spec.dims), gkind, wseed
                )
            if wdown:
                tx = _make_xbar_tx(
                    spec.offsets, spec.dims, list(spec.dims), idxs,
                    kinds, wseed,
                )
            _, h_new_ws, x_new_ws = _pallas_comm(
                xw, hw, slot, band, c, s, scale, block, down=down,
                survivor=survivor, wire_x=wx, wire_scales=wsc,
                wire_chunk=wcc, xbar_tx=tx, robust=robust,
            )
            xs = unpack(x_new_ws, spec)
            hs = unpack(h_new_ws, hspec)
            for j, i in enumerate(idxs):
                out_x[i], out_h[i] = xs[j], hs[j]

    return (
        jax.tree.unflatten(treedef, out_x),
        jax.tree.unflatten(treedef, out_h),
    )


def blocked_comm(
    x: Any,
    h: Any,
    off: jax.Array,  # int32 scalar: cyclic shift of the ownership bands
    n: int,
    s: int,
    scale,
    impl: str = "ws",
    *,
    c: Optional[int] = None,
    slot_of: Optional[jax.Array] = None,
    down: Optional[jax.Array] = None,
    arrived: Optional[jax.Array] = None,
    correct: bool = True,
    block: int = 4096,
    meshed: bool = False,
    mesh=None,
    pspecs=None,
    shard_kernels: Optional[bool] = None,
    wire: Optional[str] = None,
    wire_seed=None,
    wire_down: bool = False,
    robust: Optional[Tuple[str, int]] = None,
) -> Tuple[Any, Any]:
    """block_rs UpCom + h-update + DownCom for the blocked template.

    The old per-leaf path padded each leaf to ``(n, n, chunk)`` and
    materialized an ownership-sized delta; the sparse path gathers, per
    block column and shift ``t``, the one client row that owns it (``s``
    rolled adds, ``O(s d)`` reads) and fuses the h-update mask-free.

    ``c``/``slot_of`` generalize the template to partial participation
    (DESIGN.md §11): coordinates are chunked into ``c`` blocks (not
    ``n``) and the contiguous ownership bands are laid over the round's
    cohort *slots* — ``slot_of[i]`` is client ``i``'s slot in ``[0, c)``
    (-1 idle) — so ownership is ``(block(k) - slot_of[i] - off) mod c <
    s``: every coordinate still has exactly ``s`` owners, all of them
    cohort members.  The defaults (``c=None``, ``slot_of=None``) are full
    participation with identity slots, bit-identical to the original
    template.  ``down`` is the DownCom row mask and ``arrived``/
    ``correct`` the fault-tolerant aggregation inputs (see
    ``cyclic_comm``): a dropped owner leaves its block columns uncovered,
    and with ``correct=True`` those coordinates pass through h and x
    bitwise untouched.

    ``meshed=True`` + ``mesh`` + ``impl="pallas"``: the shard-resident
    engine (see ``cyclic_comm``) — the contiguous per-block gathers run on
    each shard's local rows and the block partials combine in one psum,
    the true reduce-scatter decomposition of the blocked uplink.

    ``wire``/``wire_seed``/``wire_down``: the quantized wire (§13); see
    ``cyclic_comm``.  ``robust``: the normalized robust-combiner spec
    (§15); see ``cyclic_comm``.
    """
    impl = effective_impl(impl, meshed=meshed, mesh=mesh)
    off = jnp.asarray(off, jnp.int32)
    m = n if c is None else int(c)
    # fold the shift into per-client slots ((slot + band) mod m < s
    # <=> (band - slot_of - off) mod m < s, the block_uplink closed
    # form; identity slot_of recovers the original (band - i - off))
    if slot_of is None:
        if m != n:
            raise ValueError(
                f"blocked_comm with c={m} < n={n} needs slot_of (the "
                f"per-client cohort slots)"
            )
        slot = (-(jnp.arange(n, dtype=jnp.int32) + off)) % m
    else:
        slot = jnp.where(
            slot_of >= 0, (-(slot_of + off)) % m, -1
        ).astype(jnp.int32)
    faulted = arrived is not None
    survivor = faulted and correct
    if faulted:
        slot = jnp.where(
            jnp.asarray(arrived).astype(bool), slot, -1
        ).astype(jnp.int32)
    if impl == "pallas" and meshed:
        return _shard_comm(
            x, h, slot, m, s, scale, template="blocked", mesh=mesh,
            pspecs=pspecs, block=block, use_kernels=shard_kernels,
            down=down, faulted=faulted, survivor=survivor,
            wire=wire, wire_seed=wire_seed, wire_down=wire_down,
            robust=robust,
        )
    xflat, treedef = jax.tree.flatten(x)
    hflat = jax.tree.leaves(h)
    dims = [int(np.prod(a.shape[1:])) for a in xflat]
    wirep = _wire_policy(wire)
    wseed = _wire_seed(wire_seed) if wirep is not None else None
    wdown = bool(wire_down) and wirep is not None
    kinds = [_wire.resolve_kind(D, wirep) if wirep is not None else "f32"
             for D in dims]

    if impl == "dense":
        pairs = [
            _dense_blocked_leaf(
                xl, hl, slot, m, s, scale, down,
                sanitize=faulted, survivor=survivor,
                quant=_leaf_quant(kinds[i], wseed, i, dims[i]),
                down_quant=(_down_quant(kinds[i], wseed, i, dims[i])
                            if wdown else None),
                robust=robust,
            )
            for i, (xl, hl) in enumerate(zip(xflat, hflat))
        ]
        return (
            jax.tree.unflatten(treedef, [a for a, _ in pairs]),
            jax.tree.unflatten(treedef, [b for _, b in pairs]),
        )

    if impl == "pallas":
        out_x = [None] * len(xflat)
        out_h = [None] * len(xflat)
        if wirep is None:
            groups = [(None, list(range(len(xflat))))]
        else:
            gmap: dict = {}
            for i in range(len(xflat)):
                gmap.setdefault(kinds[i], []).append(i)
            groups = sorted(gmap.items())
        for gkind, idxs in groups:
            spec = workspace_spec([xflat[i] for i in idxs], wire=wirep)
            hspec = workspace_spec([hflat[i] for i in idxs])
            xw = pack([xflat[i] for i in idxs], spec)
            hw = pack([hflat[i] for i in idxs], hspec)
            band = jnp.asarray(_block_band_np(spec.dims, m))
            wx = wsc = wcc = tx = None
            if gkind is not None:
                flats = [xw[:, o:o + D]
                         for o, D in zip(spec.offsets, spec.dims)]
                wx, wsc, wcc = _wire_pack(
                    flats, idxs, list(spec.dims), gkind, wseed
                )
            if wdown:
                tx = _make_xbar_tx(
                    spec.offsets, spec.dims, list(spec.dims), idxs,
                    kinds, wseed,
                )
            _, h_new_ws, x_new_ws = _pallas_comm(
                xw, hw, slot, band, m, s, scale, block, down=down,
                survivor=survivor, wire_x=wx, wire_scales=wsc,
                wire_chunk=wcc, xbar_tx=tx, robust=robust,
            )
            xs = unpack(x_new_ws, spec)
            hs = unpack(h_new_ws, hspec)
            for j, i in enumerate(idxs):
                out_x[i], out_h[i] = xs[j], hs[j]
        return (
            jax.tree.unflatten(treedef, out_x),
            jax.tree.unflatten(treedef, out_h),
        )

    # impl == "ws": s rolled adds (contiguous per-block gathers, no pad)
    # + the fused h-update, leaf by leaf
    client_of = None
    col_ok = None
    if not meshed or robust is not None:
        # block-slot -> owner client row (idle writes land in the dropped
        # overflow slot; cohort slots are a permutation of [0, m)).
        # Robust combines need the owner-value stack even when meshed —
        # see cyclic_comm.
        client_of = (
            jnp.zeros((m + 1,), jnp.int32)
            .at[jnp.where(slot >= 0, slot, m)]
            .set(jnp.arange(n, dtype=jnp.int32))[:m]
        )
        if faulted:
            # dropped owners' slots default to row 0 in client_of —
            # col_ok gates those phantom chunk gathers
            col_ok = (
                jnp.zeros((m + 1,), bool)
                .at[jnp.where(slot >= 0, slot, m)]
                .set(True)[:m]
            )
    sl = slot[:, None]
    out_x: List[Any] = [None] * len(xflat)
    out_h: List[Any] = [None] * len(xflat)
    for i, (xl, hl) in enumerate(zip(xflat, hflat)):
        D = dims[i]
        chunk = -(-D // m)
        nf, tail = divmod(D, chunk)  # full blocks + ragged tail block
        nb = nf + (1 if tail else 0)
        xf = xl.reshape(n, D).astype(jnp.float32)
        quant = _leaf_quant(kinds[i], wseed, i, D)
        xq = xf if quant is None else quant(xf)  # wire payload; h reads xf
        # blocked ownership is block-granular: evaluate the predicate at
        # (n, nb) (tiny) and expand to coordinates with a repeat — beats
        # recomputing an (n, D) predicate (measured, DESIGN.md §9)
        jb = jnp.arange(nb, dtype=jnp.int32)[None, :]
        own_nb = _wrapped_owned(sl, jb, m, s)
        owned = jnp.repeat(own_nb, chunk, axis=1)[:, :D]
        with jax.named_scope(spans.UPLINK):
            cov = None
            if robust is not None:
                # robust combine over the s contiguous shift-gathers: stack
                # the per-shift owner rows (the same whole-chunk reads the
                # mean path accumulates) instead of summing them
                jf = jnp.arange(nf, dtype=jnp.int32)
                xm = xq[:, :nf * chunk].reshape(n, nf, chunk)
                vals_l, ok_l = [], []
                for t in range(s):
                    cf = (t - jf) % m
                    v = xm[client_of[cf], jf].reshape(-1)
                    okv = (col_ok[cf] if faulted
                           else jnp.ones((nf,), bool))
                    okv = jnp.repeat(okv, chunk)
                    if tail:
                        ct = (t - nf) % m
                        v = jnp.concatenate(
                            [v, xq[client_of[ct], nf * chunk:]])
                        okt = (col_ok[ct] if faulted else jnp.bool_(True))
                        okv = jnp.concatenate(
                            [okv, jnp.broadcast_to(okt, (tail,))])
                    vals_l.append(v)
                    ok_l.append(okv)
                x_bar, rcnt = _robust.robust_combine_stack(
                    jnp.stack(vals_l), jnp.stack(ok_l), *robust)
                if survivor:
                    cov = rcnt > 0
            elif meshed:
                # sharded client axis: keep the d-sized all-reduce shape (see
                # cyclic_comm); the predicate fuses into the partial sum
                num = jnp.where(owned, xq, 0.0).sum(axis=0)
                if survivor:
                    x_bar, cov = _survivor_bar(
                        num, owned.astype(jnp.float32).sum(axis=0)
                    )
                else:
                    x_bar = num / s
            else:
                xm = xq[:, :nf * chunk].reshape(n, nf, chunk)
                jf = jnp.arange(nf, dtype=jnp.int32)
                acc = jnp.zeros((nf, chunk), jnp.float32)
                acc_t = jnp.zeros((tail,), jnp.float32)
                cnt_f = jnp.zeros((nf,), jnp.float32)
                cnt_t = jnp.zeros((), jnp.float32)
                for t in range(s):
                    # owner row of block j at shift t: the client whose slot
                    # is (t - j) mod m — one contiguous chunk per block, the
                    # reduce-scatter shape
                    if faulted:
                        ok = col_ok[(t - jf) % m]
                        acc = acc + jnp.where(
                            ok[:, None], xm[client_of[(t - jf) % m], jf], 0.0
                        )
                        cnt_f = cnt_f + ok.astype(jnp.float32)
                    else:
                        acc = acc + xm[client_of[(t - jf) % m], jf]
                    if tail:
                        if faulted:
                            ok_t = col_ok[(t - nf) % m]
                            acc_t = acc_t + jnp.where(
                                ok_t, xq[client_of[(t - nf) % m],
                                         nf * chunk:], 0.0
                            )
                            cnt_t = cnt_t + ok_t.astype(jnp.float32)
                        else:
                            acc_t = acc_t + xq[client_of[(t - nf) % m],
                                               nf * chunk:]
                num = jnp.concatenate([acc.reshape(-1), acc_t]) \
                    if tail else acc.reshape(-1)
                if survivor:
                    cnt = jnp.repeat(cnt_f, chunk)
                    if tail:
                        cnt = jnp.concatenate(
                            [cnt, jnp.broadcast_to(cnt_t, (tail,))]
                        )
                    x_bar, cov = _survivor_bar(num, cnt)
                else:
                    x_bar = num / s
        if wdown:
            x_bar = _down_quant(kinds[i], wseed, i, D)(x_bar)
        out_x[i], out_h[i] = _finish_leaf(xl, hl, xf, x_bar, owned, scale,
                                          down, cov)
    return (
        jax.tree.unflatten(treedef, out_x),
        jax.tree.unflatten(treedef, out_h),
    )
