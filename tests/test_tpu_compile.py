"""The chip path compiles for a TPU v5e: each kernel the TPU dispatch
selects, at the whisper-tiny client width (n=8 rows, d=36.5M), and the
shard engine on (2, 2) and (4, 1) meshes of a v5e:2x2, whose collectives
must stay d-sized.  The TPU compiler compiles for a described,
unattached chip, so nothing runs: these catch what interpret mode cannot
(block shapes Mosaic refuses, primitives it cannot lower, VMEM overruns).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  Every compiled program must hold a Mosaic kernel
(``tpu_custom_call``)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.dist import comm_ws
from repro.kernels import compress, local_step, uplink
from repro.launch import hlo_analysis

N, D = 8, 36_501_504  # client rows; whisper-tiny's parameters per client
M, S = 6, 3  # template columns (cohort) and owners per coordinate


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache(topo):
    """Compiles for a described chip cannot be read back from the
    persistent cache; keep them out of it.  Compile as the trainer runs,
    in 32-bit mode: a test that imported the convex core earlier in this
    process turned x64 on, and Pallas cannot lower these kernels under
    it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_cache):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_cases():
    f32, i32 = jnp.float32, jnp.int32
    mat, vec, row = ((N, D), f32), ((D,), f32), ((N,), i32)
    band = ((D,), i32)
    ms = ("masked_sum", lambda x, sl, b: uplink.masked_sum(
        x, sl, b, M, S, interpret=False), [mat, row, band])
    msc = ("masked_sum_counts", lambda x, sl, b: uplink.masked_sum(
        x, sl, b, M, S, counts=True, interpret=False), [mat, row, band])
    iw_args = [((N, D), jnp.int8), ((N, -(-D // 256)), f32), band, row, band]
    iw = ("int_wire_masked_sum", lambda c, sc, ch, sl, b: uplink.masked_sum(
        compress.wire_dequant(c, sc, ch), sl, b, M, S, interpret=False),
        iw_args)
    iwc = ("int_wire_masked_sum_counts", lambda c, sc, ch, sl, b:
           uplink.masked_sum(compress.wire_dequant(c, sc, ch), sl, b, M, S,
                             counts=True, interpret=False), iw_args)
    rt = ("robust_sum_trimmed", lambda x, sl, b: uplink.robust_sum(
        x, sl, b, M, S, kind="trimmed", k=1, interpret=False),
        [mat, row, band])
    rm = ("robust_sum_median", lambda x, sl, b: uplink.robust_sum(
        x, sl, b, M, S, kind="median", interpret=False), [mat, row, band])
    hu = ("h_update", lambda x, h, xb, sl, dn, b: uplink.h_update(
        x, h, xb, sl, b, M, S, 0.5, down=dn, interpret=False),
        [mat, mat, vec, row, row, band])
    huc = ("h_update_covered", lambda x, h, xb, sl, dn, b, cv:
           uplink.h_update(x, h, xb, sl, b, M, S, 0.5, down=dn, covered=cv,
                           interpret=False),
           [mat, mat, vec, row, row, band, ((D,), jnp.bool_)])
    ls = ("fused_local_step", lambda x, g, h: local_step.fused_local_step(
        x, g, h, 0.05, interpret=False),
        [((D,), jnp.bfloat16), vec, vec])
    c2 = ("compress_2d", lambda x, sl: compress.compress(
        x, sl, M, S, interpret=False), [mat, row])
    return {c[0]: c for c in (ms, msc, iw, iwc, rt, rm, hu, huc, ls, c2)}


CASES = _kernel_cases()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e_at_client_width(one_chip, name):
    _, fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
            for sh, dt in shapes]
    assert "tpu_custom_call" in _hlo(fn, *args)


@pytest.mark.parametrize("kernel", ["masked_sum", "h_update"])
def test_kernel_at_n512_fits_scoped_vmem(one_chip, kernel):
    # every tile is (n, blk): at n=512 the fixed block=4096 ran Mosaic out
    # of its default scoped VMEM; fit_block sizes it from n and the dtypes
    n, d = 512, 1 << 20

    def spec(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    sl, b = spec((n,), jnp.int32), spec((d,), jnp.int32)
    if kernel == "masked_sum":
        hlo = _hlo(lambda x, sl, b: uplink.masked_sum(
            x, sl, b, 64, 2, interpret=False), spec((n, d)), sl, b)
    else:
        hlo = _hlo(lambda x, h, xb, sl, b: uplink.h_update(
            x, h, xb, sl, b, 64, 2, 0.5, interpret=False),
            spec((n, d)), spec((n, d)), spec((d,)), sl, b)
    assert "tpu_custom_call" in hlo


# leaf views (n, R, C) the leaf-native kernels see at the cells' widths:
# stablelm's largest leaf, whisper's MLP leaves and its padded embedding
LEAF_VIEWS = {"stablelm_mlp": (4, 2560, 6912), "whisper_mlp": (8, 1536, 1536),
              "whisper_embed": (8, 51968, 384)}


def _leaf_case(kernel, n, R, C):
    """(fn, shapes, donate) of one leaf kernel over an ``(n, R, C)`` view;
    h_update's donated x and h come back first, as they are aliased."""
    f32, i32 = jnp.float32, jnp.int32
    leaf, plane, row = ((n, R, C), f32), ((R, C), f32), ((n,), i32)
    tmpl = "blocked" if n == 4 else "cyclic"
    m, s = min(n, M), 2
    if kernel.startswith("leaf_masked_sum"):
        counts = kernel.endswith("counts")
        return (lambda x, sl: uplink.leaf_masked_sum(
            x, sl, m, s, template=tmpl, counts=counts, interpret=False),
            [leaf, row], ())
    if kernel == "leaf_h_update":
        return (lambda x, h, xb, sl, dn: uplink.leaf_h_update(
            x, h, xb, sl, m, s, 0.5, template=tmpl, down=dn,
            interpret=False)[::-1], [leaf, leaf, plane, row, row], (0, 1))
    return (lambda x, h, xb, sl, dn, cv: uplink.leaf_h_update(
        x, h, xb, sl, m, s, 0.5, template=tmpl, down=dn, covered=cv,
        interpret=False)[::-1],
        [leaf, leaf, plane, row, row, ((R, C), jnp.bool_)], (0, 1))


@pytest.mark.parametrize("view", sorted(LEAF_VIEWS))
@pytest.mark.parametrize("kernel", [
    "leaf_masked_sum", "leaf_masked_sum_counts", "leaf_h_update",
    "leaf_h_update_covered"])
def test_leaf_kernel_compiles_in_place_at_cell_width(one_chip, kernel, view):
    """Mosaic takes the leaf-native kernels at the cells' leaf views, and
    h_update updates the donated x and h in place: the program's
    temporaries stay below one leaf (no copy of x or h)."""
    n, R, C = LEAF_VIEWS[view]
    fn, shapes, donate = _leaf_case(kernel, n, R, C)
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
            for sh, dt in shapes]
    comp = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    assert "tpu_custom_call" in comp.as_text()
    assert comp.memory_analysis().temp_size_in_bytes < n * R * C * 4


def _shard_engine_hlo(topo, shape, monkeypatch):
    """The §10 shard engine's cyclic comm step at the whisper-tiny width,
    compiled for a ``shape`` (data, model) mesh of the described chips,
    client rows split over ``data``."""
    # the dispatch reads the backend; steer it to the chip branch here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices).reshape(shape), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    rows = NamedSharding(mesh, P("data"))
    leaves = {"embed": (N, 51865 * 384), "rest": (N, D - 51865 * 384)}
    x = {k: jax.ShapeDtypeStruct(sh, jnp.bfloat16, sharding=rows)
         for k, sh in leaves.items()}
    h = {k: jax.ShapeDtypeStruct(sh, jnp.float32, sharding=rows)
         for k, sh in leaves.items()}
    slot = jax.ShapeDtypeStruct((N,), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    return _hlo(lambda x, h, sl: comm_ws.cyclic_comm(
        x, h, sl, M, S, 0.5, impl="pallas", meshed=True, mesh=mesh,
        shard_kernels=True), x, h, slot)


def _assert_d_sized_psum_only(hlo):
    assert "tpu_custom_call" in hlo
    assert "all-reduce" in hlo  # the d-sized psum of the partials
    # nothing population-sized crosses the interconnect: N * D here
    worst = hlo_analysis.max_collective_elems(hlo)
    assert 0 < worst <= D, worst


def test_shard_engine_compiles_on_2x2_mesh(topo, no_cache, monkeypatch):
    _assert_d_sized_psum_only(_shard_engine_hlo(topo, (2, 2), monkeypatch))


def test_shard_engine_compiles_on_4x1_mesh(topo, no_cache, monkeypatch):
    _assert_d_sized_psum_only(_shard_engine_hlo(topo, (4, 1), monkeypatch))
