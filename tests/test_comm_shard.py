"""Shard-resident comm engine invariants (DESIGN.md §10).

Multi-device coverage of ``comm_ws`` meshed-pallas (the shard_map'd
engine), run through the ``subproc`` fixture (device counts must be fixed
before jax init):

* equivalence vs ``impl="dense"`` to <= 1e-6 across mesh shapes (1x8,
  4x2, 8x1), ragged leaf d, idle clients, s == c, a client axis that does
  NOT divide the dp extent (engine pads with idle rows), both uplinks,
  and both per-shard modes (fused-jnp gathers and interpret-mode Pallas
  kernels inside the shard_map),
* model-parallel ``pspecs``: leaves sharded over the model axis keep
  their shards (per-shard bands from the global coordinate index),
* HLO regression: the lowered meshed-pallas ``make_comm_step`` contains
  NO ``(n, d)``-sized all-gather / all-reduce — collectives stay d-sized
  — while the known-bad composition (whole-array pallas workspace on a
  dp-sharded client axis, the thing PR 3 demoted and this engine fixes)
  is the positive control that does all-gather ``(n, d)``.

Single-device hypothesis sweeps of the same engine live in
tests/test_comm_ws.py (1x1 mesh).
"""

import pytest


def test_shard_engine_matches_dense_across_meshes(subproc):
    subproc("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.dist import comm_ws

def tree(rng, n):
    # ragged dims, a reshaped leaf, a bf16 leaf, a tall-regime candidate
    x = {"w": jnp.asarray(rng.normal(size=(n, 13, 5)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(n, 1)), jnp.bfloat16),
         "v": jnp.asarray(rng.normal(size=(n, 29)), jnp.float32)}
    h = {k: jnp.asarray(rng.normal(size=a.shape), jnp.float32)
         for k, a in x.items()}
    h = jax.tree.map(lambda a: a - a.mean(axis=0, keepdims=True), h)
    return x, h

def slot_of(rng, n, c):
    cohort = rng.choice(n, size=c, replace=False)
    out = np.full((n,), -1, np.int32)
    out[cohort] = rng.permutation(c)
    return jnp.asarray(out)

def maxerr(a, b):
    return max(
        float(jnp.abs(u.astype(jnp.float32) - v.astype(jnp.float32)).max())
        for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

# (n, c, s): idle clients (c < n), s == c (no compression), and client
# axes that do not divide the dp extent (6 and 9 on 4- and 8-way dp)
CASES = [(8, 5, 2), (6, 4, 4), (9, 3, 3), (2, 2, 2)]
for shape in [(1, 8), (4, 2), (8, 1)]:
    mesh = jax.make_mesh(shape, ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    dp = shape[0]
    for n, c, s in CASES:
        rng = np.random.default_rng(n * 100 + c * 10 + s + shape[0])
        x, h = tree(rng, n)
        sh = NamedSharding(mesh, P("data") if n % dp == 0 else P())
        xs = jax.tree.map(lambda a: jax.device_put(a, sh), x)
        hs = jax.tree.map(lambda a: jax.device_put(a, sh), h)
        slot = slot_of(rng, n, c)
        off = jnp.asarray(int(rng.integers(0, n)), jnp.int32)
        xd, hd = comm_ws.cyclic_comm(x, h, slot, c, s, 0.37, impl="dense")
        bd = comm_ws.blocked_comm(x, h, off, n, s, 0.37, impl="dense")
        for sk in (False, True):  # jnp gathers / interpret kernels
            xn, hn = jax.jit(lambda xs, hs, sk=sk: comm_ws.cyclic_comm(
                xs, hs, slot, c, s, 0.37, impl="pallas", meshed=True,
                mesh=mesh, shard_kernels=sk, block=16))(xs, hs)
            assert maxerr(xd, xn) <= 1e-6, ("cyc", shape, n, c, s, sk)
            assert maxerr(hd, hn) <= 1e-6, ("cyc", shape, n, c, s, sk)
            xb, hb = jax.jit(lambda xs, hs, sk=sk: comm_ws.blocked_comm(
                xs, hs, off, n, s, 0.37, impl="pallas", meshed=True,
                mesh=mesh, shard_kernels=sk, block=16))(xs, hs)
            assert maxerr(bd[0], xb) <= 1e-6, ("blk", shape, n, c, s, sk)
            assert maxerr(bd[1], hb) <= 1e-6, ("blk", shape, n, c, s, sk)
print("OK")
""", devices=8, timeout=1500)


def test_shard_engine_model_parallel_pspecs(subproc):
    """Leaves sharded over the model axis enter the shard_map sharded
    (no resharding) and the per-shard bands come from the global
    coordinate index — equivalence vs dense stays exact."""
    subproc("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.dist import comm_ws

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
n, c, s = 4, 3, 2
rng = np.random.default_rng(1)
x = {"a": jnp.asarray(rng.normal(size=(n, 5, 8)), jnp.float32),
     "b": jnp.asarray(rng.normal(size=(n, 6, 7)), jnp.float32),
     "c": jnp.asarray(rng.normal(size=(n, 9)), jnp.float32)}
h = {k: jnp.asarray(rng.normal(size=a.shape), jnp.float32)
     for k, a in x.items()}
pspecs = {"a": P("data", None, "model"), "b": P("data", "model", None),
          "c": P("data", None)}
put = lambda t: jax.tree.map(
    lambda a, sp: jax.device_put(a, NamedSharding(mesh, sp)), t, pspecs)
xs, hs = put(x), put(h)
sl = np.full((n,), -1, np.int32)
cohort = rng.choice(n, size=c, replace=False)
sl[cohort] = rng.permutation(c)
slot = jnp.asarray(sl)
off = jnp.asarray(2, jnp.int32)

def maxerr(a, b):
    return max(
        float(jnp.abs(u.astype(jnp.float32) - v.astype(jnp.float32)).max())
        for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

xd, hd = comm_ws.cyclic_comm(x, h, slot, c, s, 0.37, impl="dense")
bd = comm_ws.blocked_comm(x, h, off, n, s, 0.37, impl="dense")
for sk in (False, True):
    xn, hn = jax.jit(lambda xs, hs, sk=sk: comm_ws.cyclic_comm(
        xs, hs, slot, c, s, 0.37, impl="pallas", meshed=True, mesh=mesh,
        pspecs=pspecs, shard_kernels=sk, block=8))(xs, hs)
    assert maxerr(xd, xn) <= 1e-6 and maxerr(hd, hn) <= 1e-6, sk
    xb, hb = jax.jit(lambda xs, hs, sk=sk: comm_ws.blocked_comm(
        xs, hs, off, n, s, 0.37, impl="pallas", meshed=True, mesh=mesh,
        pspecs=pspecs, shard_kernels=sk, block=8))(xs, hs)
    assert maxerr(bd[0], xb) <= 1e-6 and maxerr(bd[1], hb) <= 1e-6, sk
print("OK")
""", devices=8)


def test_no_population_sized_collective_in_meshed_pallas(subproc):
    """The point of the shard engine: the lowered meshed-pallas comm step
    moves d-sized partials only.  Parse every collective's result shape in
    the compiled HLO for both uplinks and assert the largest stays d-sized
    (never (n, d)-sized); the sparse-gather path run non-meshed on a
    dp-sharded client axis (what PR 3 measured as the gather-turned-
    all-reduce failure) is the positive control whose collective scales
    with s*d, validating the parser."""
    subproc("""
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.transformer import ModelConfig
from repro.dist import comm_ws, sharding, tamuna_dp
# the largest collective result (tuple results read element by element)
from repro.launch.hlo_analysis import max_collective_elems as max_coll_elems

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = ModelConfig(family="dense", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab=128, dtype=jnp.float32,
                  remat=False)
n = sharding.n_clients(mesh)
params = jax.eval_shape(
    lambda: __import__("repro.dist.model_api", fromlist=["init"]).init(
        jax.random.key(0), cfg))
d_total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
for uplink in ("masked_psum", "block_rs"):
    c = n if uplink == "block_rs" else 3
    tcfg = tamuna_dp.DistTamunaConfig(gamma=0.05, c=c, s=2, p=0.5,
                                      uplink=uplink, comm_impl="pallas")
    state = tamuna_dp.init_state(jax.random.key(0), cfg, mesh, tcfg)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                      tamuna_dp.state_pspecs(state, cfg, mesh),
                      is_leaf=lambda x: isinstance(x, P))
    state = jax.device_put(state, sh)
    step = tamuna_dp.make_comm_step(cfg, tcfg, mesh)
    # off the TPU the per-shard kernels are off: every leaf on jnp
    assert step.route_coords == {"leaf": 0, "workspace": 0,
                                 "jnp": d_total}, step.route_coords
    fn = jax.jit(step)
    hlo = fn.lower(state, jax.random.key(0)).compile().as_text()
    worst = max_coll_elems(hlo)
    # d-sized collectives only: the engine's psum of the concatenated
    # partials is <= d_total elements per model shard; allow 2x headroom
    # for key/slot bookkeeping, but nothing population-scaled (n*d here
    # is 4*d_total)
    assert 0 < worst <= 2 * d_total, (uplink, worst, d_total)

# positive control (parser + the failure this engine removes): the sparse
# gather run NON-meshed on a dp-sharded client axis lowers its UpCom to
# an s*D-sized all-reduce (PR 3's measured regression), not a d-sized one
D = 1024
x = {"w": jnp.zeros((n, D), jnp.float32)}
h = {"w": jnp.zeros((n, D), jnp.float32)}
xs = jax.tree.map(
    lambda a: jax.device_put(a, NamedSharding(mesh, P("data"))), x)
hs = jax.tree.map(
    lambda a: jax.device_put(a, NamedSharding(mesh, P("data"))), h)
slot = jnp.asarray(np.r_[np.arange(3), [-1] * (n - 3)].astype(np.int32))
bad = jax.jit(lambda xs, hs: comm_ws.cyclic_comm(
    xs, hs, slot, 3, 2, 0.37, impl="ws", meshed=False, block=256))
worst = max_coll_elems(bad.lower(xs, hs).compile().as_text())
assert worst >= 2 * D, worst  # s * D with s=2
print("OK")
""", devices=8)


@pytest.mark.parametrize("shape", [(1, 1), (4, 2)])
def test_shard_engine_leaf_route_matches_dense(subproc, shape):
    """Leaves of ``LEAF_MIN`` coordinates a row or more take the
    leaf-native kernels (interpret mode here) in their own layouts: 2-D,
    3-D and 4-D stacked leaves with C not a multiple of 128, beside a
    small leaf on the jnp route; both templates, all rows, c < n with a
    DownCom mask, and the survivor rebuild under dropped owners — all to
    <= 1e-6 of dense, on one shard and on 4 client shards."""
    subproc(f"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.dist import comm_ws

mesh = jax.make_mesh({shape!r}, ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
n, c, s = 8, 5, 2
rng = np.random.default_rng(7)
trails = {{"a": (257, 260), "b": (2, 33, 1000), "v": (70001,), "t": (29,)}}
x = {{k: jnp.asarray(rng.normal(size=(n,) + t), jnp.float32)
     for k, t in trails.items()}}
h = {{k: jnp.asarray(rng.normal(size=a.shape), jnp.float32)
     for k, a in x.items()}}
sh = NamedSharding(mesh, P("data"))
xs, hs = jax.device_put(x, sh), jax.device_put(h, sh)
big = sum(int(np.prod(t)) for t in trails.values()
          if np.prod(t) >= comm_ws.LEAF_MIN)
for tmpl in ("cyclic", "blocked"):
    got = comm_ws.route_coords(x, tmpl, c, s, impl="pallas", mesh=mesh,
                               shard_kernels=True)
    assert got == {{"leaf": big, "workspace": 0, "jnp": 29}}, got

def maxerr(a, b):
    return max(
        float(jnp.abs(u.astype(jnp.float32) - v.astype(jnp.float32)).max())
        for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

sl = np.full((n,), -1, np.int32)
sl[rng.choice(n, size=c, replace=False)] = rng.permutation(c)
slot = jnp.asarray(sl)
down = jnp.asarray(rng.integers(0, 2, size=n).astype(bool))
arrived = jnp.asarray((sl != 0) & (sl != 1))  # slots 0, 1: owners dropped
full = jnp.asarray(np.roll(np.arange(n, dtype=np.int32), 3))
off = jnp.asarray(2, jnp.int32)
cases = [
    dict(c=n, slot=full),
    dict(c=c, slot=slot, down=down),
    dict(c=c, slot=slot, down=down, arrived=arrived),
]
for kw in cases:
    m, sl_, kw = kw.pop("c"), kw.pop("slot"), kw
    xd, hd = comm_ws.cyclic_comm(x, h, sl_, m, s, 0.37, impl="dense", **kw)
    xn, hn = jax.jit(lambda xs, hs: comm_ws.cyclic_comm(
        xs, hs, sl_, m, s, 0.37, impl="pallas", meshed=True, mesh=mesh,
        shard_kernels=True, **kw))(xs, hs)
    assert maxerr(xd, xn) <= 1e-6 and maxerr(hd, hn) <= 1e-6, ("cyc", m)
    bkw = dict(kw, c=m, slot_of=sl_)
    bd = comm_ws.blocked_comm(x, h, off, n, s, 0.37, impl="dense", **bkw)
    xb, hb = jax.jit(lambda xs, hs: comm_ws.blocked_comm(
        xs, hs, off, n, s, 0.37, impl="pallas", meshed=True, mesh=mesh,
        shard_kernels=True, **bkw))(xs, hs)
    assert maxerr(bd[0], xb) <= 1e-6 and maxerr(bd[1], hb) <= 1e-6, \\
        ("blk", m)
print("OK")
""", devices=8)


@pytest.mark.parametrize("template", ["cyclic", "blocked"])
@pytest.mark.parametrize("wire", ["f32", "int8"])
def test_meshed_f32_comm_holds_no_workspace(template, wire):
    """HLO regression: with the kernels on, the meshed f32 comm program
    packs nothing — no concatenate into an ``(n, d_total)`` buffer and no
    int32 band constant of a leaf's size.  The quantized wire, which
    keeps the workspace for its per-chunk scales, is the positive
    control: it still concatenates the leaves and bakes their bands."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.dist import comm_ws

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    n = 4
    x = {"a": jnp.zeros((n, 257, 260)), "v": jnp.zeros((n, 70001)),
         "t": jnp.zeros((n, 29))}
    h = jax.tree.map(jnp.zeros_like, x)
    dims = [int(np.prod(a.shape[1:])) for a in jax.tree.leaves(x)]
    kw = dict(impl="pallas", meshed=True, mesh=mesh, shard_kernels=True,
              wire=wire, wire_seed=jnp.uint32(3))
    if template == "cyclic":
        slot = jnp.arange(n, dtype=jnp.int32)
        fn = lambda x, h: comm_ws.cyclic_comm(x, h, slot, n, 2, 0.5, **kw)
    else:
        fn = lambda x, h: comm_ws.blocked_comm(x, h, jnp.int32(1), n, 2,
                                               0.5, **kw)
    hlo = jax.jit(fn).lower(x, h).as_text(dialect="hlo")
    consts = {int(d) for d in re.findall(r"s32\[(\d+)\]\{0\} constant\(",
                                         hlo)}
    packed = {int(d) for _, d in re.findall(
        r"f32\[(\d+),(\d+)\]\{1,0\} concatenate\(", hlo)}
    if wire == "f32":
        assert not consts & set(dims), consts
        assert sum(dims) not in packed, packed
    else:
        assert max(dims) in consts, consts
        assert sum(dims) in packed, packed
