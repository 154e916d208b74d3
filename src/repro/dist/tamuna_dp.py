"""TAMUNA-DP: the distributed (mesh-sharded) round engine for LM training.

Where ``repro.core.tamuna`` is the paper-faithful reference over flat
vectors, this module runs the same algorithm over *arbitrary parameter
pytrees* with the client population mapped onto the mesh's data axes
(client i == data-shard i, see ``repro.dist.sharding``).  A round is

  L x local  ``make_local_step``: per-client grads + local update.  No
             cross-client collectives — the common case is all-local.
  1 x comm   ``make_comm_step``: the only communication of the algorithm.
             ``uplink="masked_psum"``: permutation-masked sum over the
             client axis (each coordinate uploaded by exactly ``s`` of the
             ``c`` cohort members, reconstructed as ``(1/s) * psum``).
             ``uplink="block_rs"``: the contiguous-block template of
             ``masks.block_template_mask`` — the reduce-scatter-shaped
             variant (see ``block_uplink`` and DESIGN.md §3).

State leaves are stacked per client: ``x``/``h`` leaves are ``(n, *param)``
and shard over the data axes, so the masked sum lowers to an all-reduce
(psum) over clients and the blocked variant to reduce-scatter-shaped
collectives — communication scales with the cohort, never with tokens.

Both uplinks aggregate mask-free through ``repro.dist.comm_ws``: ownership
comes from static closed-form band tables fused into the aggregation
(``comm_impl="ws"``, meshed mode: the UpCom keeps the d-sized psum shape
since clients are device-sharded here) or the packed-workspace Pallas
kernels (``"pallas"``, TPU), with the per-leaf dense-mask reference
retained as ``comm_impl="dense"`` (DESIGN.md §9).

Partial participation is *elastic* (DESIGN.md §11): ``round_cohort``
derives the round's cohort from the comm key, ``gather_cohort`` /
``scatter_cohort`` give the round engine O(c·L) local compute, both
uplinks run at any ``c <= n`` (the blocked bands lie over cohort slots),
and the comm step's DownCom can target just the next round's cohort.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import spans
from repro.core import masks, theory
from repro.dist import comm_ws, model_api, robust as _robust, \
    sharding, wire
from repro.models.transformer import ModelConfig
from repro.optim import optimizers

__all__ = [
    "DistTamunaConfig",
    "DistTamunaState",
    "init_state",
    "state_pspecs",
    "round_cohort",
    "member_mask",
    "gather_cohort",
    "scatter_cohort",
    "make_local_step",
    "make_comm_step",
    "sample_round_length",
]


@dataclasses.dataclass(frozen=True)
class DistTamunaConfig:
    gamma: float  # local stepsize (AdamW lr when local_opt="adamw")
    c: int  # cohort size (2 <= c <= n)
    s: int  # sparsity index (2 <= s <= c); s == c disables compression
    p: float  # inverse expected local steps per round
    eta: Optional[float] = None  # control stepsize; None -> Remark 2 default
    uplink: str = "masked_psum"  # "masked_psum" | "block_rs"
    microbatches: int = 1  # gradient accumulation steps per local step
    local_opt: str = "sgd"  # "sgd" (paper rule) | "adamw" (DESIGN.md §7)
    use_kernel: bool = False  # fused Pallas local-step update (kernels/)
    comm_impl: str = "auto"  # "auto" | "dense" | "ws" | "pallas" (§9)
    wire_precision: str = "f32"  # UpCom payload width (§13): "auto" |
    #   "f32" | "bf16" | "f16" | "int8" | "int4" — "f32" is bitwise the
    #   unquantized path, "auto" resolves per leaf size
    wire_down: bool = False  # also quantize the DownCom broadcast (§13)
    robust_agg: str = "mean"  # per-coordinate combiner (§15): "mean" |
    #   "trimmed" (trim_k per side) | "median" — "mean" (and trimmed at
    #   k=0) is bitwise the existing arrived-owner-mean path
    trim_k: int = 0  # values trimmed per side under robust_agg="trimmed"

    def __post_init__(self):
        if not (2 <= self.s <= self.c):
            raise ValueError(f"need 2 <= s <= c, got s={self.s} c={self.c}")
        if self.wire_precision not in wire.WIRE_POLICIES:
            raise ValueError(
                f"unknown wire_precision {self.wire_precision!r}; want one "
                f"of {wire.WIRE_POLICIES}"
            )
        if self.wire_down and not wire.is_wire(self.wire_precision):
            raise ValueError(
                "wire_down quantizes the DownCom broadcast; it needs a "
                f"non-f32 wire_precision, got {self.wire_precision!r}"
            )
        if self.uplink not in ("masked_psum", "block_rs"):
            raise ValueError(f"unknown uplink {self.uplink!r}")
        if self.comm_impl not in comm_ws.COMM_IMPLS:
            raise ValueError(
                f"unknown comm_impl {self.comm_impl!r}; want one of "
                f"{comm_ws.COMM_IMPLS}"
            )
        if self.local_opt not in ("sgd", "adamw"):
            raise ValueError(f"unknown local_opt {self.local_opt!r}")
        if self.use_kernel and self.local_opt != "sgd":
            raise ValueError(
                "use_kernel fuses the paper's SGD rule; it does not apply "
                f"to local_opt={self.local_opt!r}"
            )
        # validates robust_agg/trim_k against s (raises on bad specs)
        _robust.normalize_robust(self.robust_agg, self.trim_k, self.s)

    def robust_(self):
        """The normalized robust-combiner spec the comm impls consume:
        ``None`` (bitwise mean path) or ``("trimmed", k)``/``("median",
        0)`` — see ``repro.dist.robust.normalize_robust``."""
        return _robust.normalize_robust(self.robust_agg, self.trim_k,
                                        self.s)

    def eta_(self, n: int) -> float:
        """Control-variate stepsize: Remark 2's largest valid
        ``eta = p * chi_max(n, s)`` — same rule as the reference core's
        ``theory.TunedParams``."""
        if self.eta is not None:
            return self.eta
        return theory.recommended_eta(self.p, max(n, 2), self.s)


class DistTamunaState(NamedTuple):
    x: Any  # client-stacked params: leaves (n, *param_shape)
    h: Any  # control variates, f32, same structure; sum_i h_i == 0
    opt: Any  # local-optimizer state (() for sgd)
    round: jax.Array  # int32 scalar
    up_floats: jax.Array  # f32 scalar: cumulative uplink floats per client
    down_floats: jax.Array  # f32 scalar
    # dtype-aware wire accounting (§13): cumulative wire BYTES per client,
    # resolved from the per-leaf wire kinds at builder time.  On the f32
    # wire these are byte-identical to floats * 4.
    up_bytes: jax.Array = None  # f32 scalar
    down_bytes: jax.Array = None  # f32 scalar


# --------------------------------------------------------------------------
# init / sharding
# --------------------------------------------------------------------------


def init_state(
    key: jax.Array, cfg: ModelConfig, mesh: Mesh, tcfg: DistTamunaConfig,
    n: Optional[int] = None,
) -> DistTamunaState:
    """Client-stacked initial state.  ``n`` overrides the mesh-derived
    population (``sharding.n_clients``) for placements that stack more
    clients than devices — the client axis then holds ``n / dp`` rows per
    shard (single-device simulators pass a 1x1 mesh and any ``n``)."""
    n = n or sharding.n_clients(mesh)
    if tcfg.c > n:
        raise ValueError(f"cohort c={tcfg.c} exceeds population n={n}")
    params = model_api.init(key, cfg)
    x = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), params
    )
    h = jax.tree.map(
        lambda a: jnp.zeros((n,) + a.shape, jnp.float32), params
    )
    opt: Any = ()
    if tcfg.local_opt == "adamw":
        # elementwise moments live on the stacked leaves directly
        opt = optimizers.adamw(tcfg.gamma).init(x)
    return DistTamunaState(
        x=x, h=h, opt=opt,
        round=jnp.zeros((), jnp.int32),
        up_floats=jnp.zeros((), jnp.float32),
        down_floats=jnp.zeros((), jnp.float32),
        up_bytes=jnp.zeros((), jnp.float32),
        down_bytes=jnp.zeros((), jnp.float32),
    )


def state_pspecs(
    state: DistTamunaState, cfg: ModelConfig, mesh: Mesh
) -> DistTamunaState:
    """PartitionSpec pytree matching ``state`` exactly (scalars -> P())."""
    x_spec = sharding.stacked_params_pspecs(state.x, cfg, mesh)
    h_spec = sharding.stacked_params_pspecs(state.h, cfg, mesh)
    opt_spec: Any = ()
    if isinstance(state.opt, optimizers.AdamState):
        opt_spec = optimizers.AdamState(
            mu=sharding.stacked_params_pspecs(state.opt.mu, cfg, mesh),
            nu=sharding.stacked_params_pspecs(state.opt.nu, cfg, mesh),
            count=P(),
        )
    return DistTamunaState(
        x=x_spec, h=h_spec, opt=opt_spec,
        round=P(), up_floats=P(), down_floats=P(),
        up_bytes=P(), down_bytes=P(),
    )


# --------------------------------------------------------------------------
# local step
# --------------------------------------------------------------------------


def _client_grads(cfg: ModelConfig, x, batch, microbatches: int):
    """Per-client losses (n,) and grads (stacked tree) with optional
    gradient accumulation; exact mean over equal-size microbatches."""

    def loss0(params, b):
        return model_api.loss(params, cfg, **b)[0]

    gfun = jax.vmap(jax.value_and_grad(loss0))

    if microbatches == 1:
        return gfun(x, batch)

    M = microbatches

    def split(a):
        nb = a.shape[1]
        assert nb % M == 0, (nb, M)
        return jnp.swapaxes(
            a.reshape((a.shape[0], M, nb // M) + a.shape[2:]), 0, 1
        )

    mbs = jax.tree.map(split, batch)

    def body(carry, mb):
        tot_l, tot_g = carry
        l, g = gfun(x, mb)
        return (tot_l + l, jax.tree.map(jnp.add, tot_g, g)), None

    n = jax.tree.leaves(x)[0].shape[0]
    init = (
        jnp.zeros((n,), jnp.float32),
        jax.tree.map(lambda a: jnp.zeros((n,) + a.shape[1:], jnp.float32),
                     x),
    )
    (tot_l, tot_g), _ = jax.lax.scan(body, init, mbs)
    inv = 1.0 / M
    return tot_l * inv, jax.tree.map(lambda g: g * inv, tot_g)


def make_local_step(cfg: ModelConfig, tcfg: DistTamunaConfig):
    """Build ``fn(state, *, tokens, labels, ...) -> (state, metrics)``.

    The paper's local iteration ``x <- x - gamma*(g - h)`` (optionally via
    the fused Pallas kernel), or an AdamW step on the h-corrected gradient.
    Zero cross-client communication: everything is client-elementwise.
    """
    gamma = tcfg.gamma
    opt = optimizers.adamw(gamma) if tcfg.local_opt == "adamw" else None

    def fn(
        state: DistTamunaState,
        *,
        tokens: jax.Array,
        labels: jax.Array,
        prefix_embeds: Optional[jax.Array] = None,
        frames: Optional[jax.Array] = None,
    ) -> Tuple[DistTamunaState, Dict[str, jax.Array]]:
        batch = {"tokens": tokens, "labels": labels}
        if prefix_embeds is not None:
            batch["prefix_embeds"] = prefix_embeds
        if frames is not None:
            batch["frames"] = frames

        losses, grads = _client_grads(cfg, state.x, batch, tcfg.microbatches)

        if tcfg.local_opt == "adamw":
            eff = jax.tree.map(
                lambda g, h: g.astype(jnp.float32) - h.astype(jnp.float32),
                grads, state.h,
            )
            x_new, opt_new = opt.update(eff, state.opt, state.x)
        elif tcfg.use_kernel:
            from repro.kernels import ops as kops

            x_new = jax.tree.map(
                lambda x, g, h: kops.fused_local_step(x, g, h, gamma),
                state.x, grads, state.h,
            )
            opt_new = state.opt
        else:
            x_new = jax.tree.map(
                lambda x, g, h: (
                    x.astype(jnp.float32)
                    - gamma * (g.astype(jnp.float32) - h.astype(jnp.float32))
                ).astype(x.dtype),
                state.x, grads, state.h,
            )
            opt_new = state.opt

        metrics = {"loss": losses.mean().astype(jnp.float32)}
        return state._replace(x=x_new, opt=opt_new), metrics

    return fn


# --------------------------------------------------------------------------
# comm step
# --------------------------------------------------------------------------


def _as_key(key: jax.Array) -> jax.Array:
    """Accept typed PRNG keys or raw (2,) uint32 key data."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return key
    return jax.random.wrap_key_data(key)


# --------------------------------------------------------------------------
# cohort plan (elastic partial participation, DESIGN.md §11)
# --------------------------------------------------------------------------


def round_cohort(key: jax.Array, n: int, c: int) -> jax.Array:
    """The round's sorted ``(c,)`` cohort, derived from the round's COMM
    key (the same key ``make_comm_step`` consumes): uniform without
    replacement.  The single source of truth for who participates — the
    round engine gathers these rows for local compute, the data pipeline
    samples batches for them, and ``make_comm_step`` (given ``cohort=None``)
    re-derives the identical set, so every layer agrees by construction.
    Replayable from ``(comm_key_base, round)`` alone via
    ``rounds.comm_round_key``.  Non-uniform (availability-driven) plans
    come from the host instead: ``repro.dist.cohort.CohortPlan``."""
    k_cohort, _ = jax.random.split(_as_key(key))
    return jnp.sort(
        jax.random.choice(k_cohort, n, shape=(c,), replace=False)
    ).astype(jnp.int32)


def member_mask(cohort: jax.Array, n: int) -> jax.Array:
    """``(n,)`` bool membership of a ``(c,)`` cohort index array."""
    return jnp.zeros((n,), bool).at[cohort].set(True)


@jax.named_scope(spans.COHORT_GATHER)
def gather_cohort(state: DistTamunaState,
                  cohort: jax.Array) -> DistTamunaState:
    """Gather the cohort's rows of x / h / opt moments into a compact
    ``(c, ...)``-stacked state (scalars shared).  Local compute on the
    result is O(c), not O(n) — idle clients do nothing, the paper's PP
    semantics."""
    take = lambda a: jnp.take(a, cohort, axis=0)
    opt: Any = state.opt
    if isinstance(opt, optimizers.AdamState):
        opt = optimizers.AdamState(
            mu=jax.tree.map(take, opt.mu),
            nu=jax.tree.map(take, opt.nu),
            count=opt.count,
        )
    return state._replace(
        x=jax.tree.map(take, state.x),
        h=jax.tree.map(take, state.h),
        opt=opt,
    )


@jax.named_scope(spans.COHORT_SCATTER)
def scatter_cohort(state: DistTamunaState, compact: DistTamunaState,
                   cohort: jax.Array) -> DistTamunaState:
    """Scatter a compact cohort state back into the full ``(n, ...)``
    rows (the inverse of ``gather_cohort``); idle rows pass through
    untouched.  Under donation the ``.at[].set`` updates write only the
    cohort rows in place."""
    put = lambda full, part: full.at[cohort].set(part)
    opt: Any = state.opt
    if isinstance(opt, optimizers.AdamState):
        opt = optimizers.AdamState(
            mu=jax.tree.map(put, opt.mu, compact.opt.mu),
            nu=jax.tree.map(put, opt.nu, compact.opt.nu),
            count=compact.opt.count,
        )
    return state._replace(
        x=jax.tree.map(put, state.x, compact.x),
        h=jax.tree.map(put, state.h, compact.h),
        opt=opt,
    )


def make_comm_step(
    cfg: ModelConfig,
    tcfg: DistTamunaConfig,
    mesh: Mesh,
    *,
    impl: Optional[str] = None,
    block: int = 4096,
    n: Optional[int] = None,
    with_stats: bool = False,
):
    """Build ``fn(state, key, cohort=None, down=None) -> state``: UpCom +
    DownCom of one round.

    masked_psum: sum the masked client vectors over the data axes (an
    all-reduce of the *sparse* contributions), reconstruct ``x_bar`` with
    the exact ``1/s`` factor, update the cohort's control variates on the
    masked coordinates only, and DownCom ``x_bar`` back down.

    block_rs: the contiguous-block template, now at any ``c <= n``
    (DESIGN.md §11): coordinates chunk into ``c`` blocks whose shifted
    ownership bands lie over the cohort's slots — still reduce-scatter
    shaped, still exactly ``s`` owners per coordinate, all of them
    participants.

    ``cohort`` is the round's ``(c,)`` client set; ``None`` derives it
    from ``key`` via ``round_cohort`` (the same derivation the elastic
    round engine uses, so engine and standalone callers agree).  ``down``
    is the DownCom row mask — the elastic engine passes the NEXT round's
    cohort (only joining clients download, the paper's DownCom); ``None``
    broadcasts ``x_bar`` to every row (full-participation behaviour).
    ``arrived``/``correct`` are the fault-tolerant aggregation inputs
    (DESIGN.md §12, ``comm_ws`` docstring): clients outside ``arrived``
    contribute nothing, the corrected rebuild divides by the arrived
    owner count, and the uplink float accounting scales to the arrived
    cohort fraction (the expected-survivor correction).

    The aggregation math runs over the flat comm workspace
    (``repro.dist.comm_ws``, DESIGN.md §9): ``impl`` (default
    ``tcfg.comm_impl``) picks fused-jnp (``"ws"``), Pallas kernels
    (``"pallas"``), or the per-leaf dense-mask reference (``"dense"``);
    ``"auto"`` resolves per backend.  All impls consume the same key and
    produce the same coordinates to float roundoff.

    Uplink/downlink float accounting is a builder-time constant (the leaf
    dims are static), not recomputed inside the traced step.

    ``with_stats=True`` makes ``fn`` return ``(state, stats)`` where
    ``stats["uncovered"]`` is the round's count of coordinates with no
    surviving owner (``comm_ws.uncovered_coords`` over the same slot
    assignment the aggregation used) — the coverage-loss observable the
    pipelined driver traces per round (DESIGN.md §14).
    """
    n = n or sharding.n_clients(mesh)
    c, s = tcfg.c, tcfg.s
    if c > n:
        raise ValueError(f"cohort c={c} exceeds population n={n}")
    eta = tcfg.eta_(n)
    scale = eta / tcfg.gamma
    impl = comm_ws.resolve_impl(impl or tcfg.comm_impl)

    # builder-time communication accounting: per-leaf dims are static, so
    # the traced fn only adds cached constants (the seed recomputed the
    # python sum over leaves inside every trace).  Both uplinks count the
    # COHORT's template: the blocked bands lie over the c cohort slots, so
    # a client uploads s chunks of ceil(D/c) — the seed's n-based constant
    # under-counted per-client floats whenever c < n.
    params_struct = jax.eval_shape(
        lambda: model_api.init(jax.random.key(0), cfg)
    )
    dims = [int(np.prod(a.shape)) for a in jax.tree.leaves(params_struct)]
    # the stacked state's PartitionSpecs: the shard-resident pallas engine
    # shard_maps with exactly these, so model-parallel leaves keep their
    # shards (no resharding at the shard_map boundary)
    stacked_struct = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((n,) + a.shape, a.dtype),
        params_struct,
    )
    stacked_specs = sharding.stacked_params_pspecs(stacked_struct, cfg, mesh)
    down_total = jnp.float32(sum(dims))
    if tcfg.uplink == "block_rs":
        nnzs = [masks.block_column_nnz(D, c, s) for D in dims]
    else:
        nnzs = [masks.column_nnz(D, c, s) for D in dims]
    up_total = jnp.float32(sum(nnzs))
    # dtype-aware wire-bytes accounting (§13), still builder-time: each
    # leaf's kind resolves from its static dim, so the per-round byte
    # constants fold at trace time.  Per CLIENT, like the float counters:
    # leaf_up_bytes at c=1 is one client's codes + (int kinds) its own
    # per-chunk scales; f32 resolves byte-identical to floats * 4.
    wire_active = wire.is_wire(tcfg.wire_precision)
    # the robust-combiner spec bakes into the built fn (a static python
    # tuple): mean/trimmed-k=0 normalize to None, so the default program
    # is the untouched PR 6/7 lowering, bitwise
    rspec = tcfg.robust_()
    kinds = tuple(
        wire.resolve_kind(D, tcfg.wire_precision) for D in dims
    )
    up_bytes_total = jnp.float32(sum(
        wire.leaf_up_bytes(nnz, D, 1, k)
        for nnz, D, k in zip(nnzs, dims, kinds)
    ))
    down_bytes_total = jnp.float32(sum(
        wire.leaf_down_bytes(D, k if tcfg.wire_down else "f32")
        for D, k in zip(dims, kinds)
    ))

    def bump(state, x_new, h_new, up=None, upb=None):
        upd = dict(
            x=x_new, h=h_new,
            round=state.round + 1,
            up_floats=state.up_floats + (up_total if up is None else up),
            down_floats=state.down_floats + down_total,
        )
        if state.up_bytes is not None:
            upd["up_bytes"] = state.up_bytes + (
                up_bytes_total if upb is None else upb
            )
        if state.down_bytes is not None:
            upd["down_bytes"] = state.down_bytes + down_bytes_total
        return state._replace(**upd)

    def slot_of_(cohort):
        return (
            jnp.full((n,), -1, jnp.int32)
            .at[cohort].set(jnp.arange(c, dtype=jnp.int32))
        )

    def up_arrived(slot_of, arrived):
        """Expected-survivor float accounting (DESIGN.md §12): only the
        arrived cohort members' uplinks consumed bandwidth.  The template
        splits the d coordinates' s-owner slots evenly over the c cohort
        slots, so the arrived fraction of ``up_total`` is the (exact in
        expectation, per-round approximate) survivor uplink volume.
        Returns ``(floats, bytes)``; the byte counter scales the same
        way (a dropped client ships neither codes nor scales)."""
        if arrived is None:
            return None, None
        surv = ((slot_of >= 0) & jnp.asarray(arrived).astype(bool)).sum()
        frac = surv.astype(jnp.float32) / c
        return up_total * frac, up_bytes_total * frac

    # coordinates of a client row per comm route (leaf-native kernels,
    # packed workspace, per-leaf jnp): how much of the state the
    # leaf-native kernels cover, read off the same dispatch the step runs
    route_coords = comm_ws.route_coords(
        stacked_struct, "blocked" if tcfg.uplink == "block_rs" else "cyclic",
        c, s, impl=impl, mesh=mesh, pspecs=stacked_specs,
        wire=tcfg.wire_precision, robust=rspec,
    )

    def wire_seed_(key):
        """The round's uint32 quantization seed, derived off the comm key
        on a folded-away stream: the ``jax.random.split`` draws for
        cohort/permutation/offset are untouched, so the f32 wire stays
        bitwise identical to the unquantized engine."""
        if not wire_active:
            return None
        return wire.round_seed(jax.random.fold_in(key, wire.WIRE_FOLD))

    if tcfg.uplink == "block_rs":
        from repro.dist.block_uplink import block_rs_aggregate

        def fn(state: DistTamunaState, key: jax.Array,
               cohort: Optional[jax.Array] = None,
               down: Optional[jax.Array] = None,
               arrived: Optional[jax.Array] = None,
               correct: bool = True) -> DistTamunaState:
            key = _as_key(key)
            _, k_off = jax.random.split(key)
            if cohort is None:
                cohort = round_cohort(key, n, c)
            off = jax.random.randint(k_off, (), 0, c, jnp.int32)
            slot_of = slot_of_(cohort)
            xb, hb = block_rs_aggregate(
                state.x, state.h, off, n, tcfg, eta, mesh, model_cfg=cfg,
                impl=impl, block=block, meshed=True, pspecs=stacked_specs,
                c=c, slot_of=slot_of, down=down, arrived=arrived,
                correct=correct, wire=tcfg.wire_precision,
                wire_seed=wire_seed_(key), wire_down=tcfg.wire_down,
                robust=rspec,
            )
            up, upb = up_arrived(slot_of, arrived)
            out = bump(state, xb, hb, up, upb)
            if not with_stats:
                return out
            bslot = jnp.where(
                slot_of >= 0, (-(slot_of + off)) % c, -1
            ).astype(jnp.int32)
            if arrived is not None:
                bslot = jnp.where(
                    jnp.asarray(arrived).astype(bool), bslot, -1
                )
            return out, {"uncovered": comm_ws.uncovered_coords(
                "blocked", tuple(dims), c, s, bslot
            )}

        fn.wire_kinds = kinds
        fn.route_coords = route_coords
        return fn

    def fn(state: DistTamunaState, key: jax.Array,
           cohort: Optional[jax.Array] = None,
           down: Optional[jax.Array] = None,
           arrived: Optional[jax.Array] = None,
           correct: bool = True) -> DistTamunaState:
        key = _as_key(key)
        _, k_perm = jax.random.split(key)
        if cohort is None:
            cohort = round_cohort(key, n, c)
        perm = jax.random.permutation(k_perm, c)
        slot_of = slot_of_(cohort)
        # the client's TEMPLATE column: perm[cohort slot], -1 when idle
        slot = jnp.where(
            slot_of >= 0, perm[jnp.clip(slot_of, 0)], -1
        ).astype(jnp.int32)
        # clients are sharded over the data axes here: comm_ws meshed mode
        # — the psum-shaped fused partial (ws/dense) or the shard-resident
        # engine (pallas: shard_map'd per-shard uplinks + one d-sized psum
        # of the partials; the mesh handle and state specs ride along)
        x_new, h_new = comm_ws.cyclic_comm(
            state.x, state.h, slot, c, s, scale, impl=impl, block=block,
            down=down, arrived=arrived, correct=correct,
            meshed=True, mesh=mesh, pspecs=stacked_specs,
            wire=tcfg.wire_precision, wire_seed=wire_seed_(key),
            wire_down=tcfg.wire_down, robust=rspec,
        )
        up, upb = up_arrived(slot_of, arrived)
        out = bump(state, x_new, h_new, up, upb)
        if not with_stats:
            return out
        sslot = slot
        if arrived is not None:
            sslot = jnp.where(
                jnp.asarray(arrived).astype(bool), sslot, -1
            )
        return out, {"uncovered": comm_ws.uncovered_coords(
            "cyclic", tuple(dims), c, s, sslot
        )}

    fn.wire_kinds = kinds
    fn.route_coords = route_coords
    return fn


def sample_round_length(rng: np.random.Generator, p: float,
                        max_L: int = 100_000) -> int:
    """Host-side ``L ~ Geometric(p)`` draw (each length compiles once)."""
    return int(min(rng.geometric(p), max_L))
