"""Validate the trip-count-aware HLO analyzer against known programs."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis as H

X = jax.ShapeDtypeStruct((256, 256), jnp.float32)
MM_FLOPS = 2 * 256**3


def _analyze(fn, *specs):
    return H.analyze(jax.jit(fn).lower(*specs).compile().as_text())


def test_single_matmul_flops_exact():
    r = _analyze(lambda a, b: a @ b, X, X)
    assert r.flops == MM_FLOPS
    # traffic ~ 3 buffers of 256 KB
    assert 2 * 256 * 256 * 4 <= r.bytes_accessed <= 6 * 256 * 256 * 4


def test_scan_trip_count_multiplies():
    def g(a):
        def body(c, _):
            return c @ a, None
        return jax.lax.scan(body, a, None, length=10)[0]

    r = _analyze(g, X)
    assert r.flops == 10 * MM_FLOPS


def test_nested_scan():
    def g(a):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ a, None
            c, _ = jax.lax.scan(inner, c, None, length=5)
            return c, None
        return jax.lax.scan(outer, a, None, length=3)[0]

    r = _analyze(g, X)
    assert r.flops == 15 * MM_FLOPS


def test_fori_loop_trip_count():
    def g(a):
        return jax.lax.fori_loop(0, 7, lambda i, c: c @ a, a)

    r = _analyze(g, X)
    assert r.flops == 7 * MM_FLOPS


def test_dot_general_contracting_dims():
    def g(a, b):  # batched matmul with nonstandard dims
        return jax.lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))))

    a = jax.ShapeDtypeStruct((4, 8, 16), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 16, 32), jnp.float32)
    r = _analyze(g, a, b)
    assert r.flops == 2 * 4 * 8 * 32 * 16


def test_collectives_counted_with_trips(subproc):
    subproc("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch import hlo_analysis as H
mesh = jax.make_mesh((4,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
def g(a):
    def body(c, _):
        y = c @ a
        return y / y.sum(), None
    return jax.lax.scan(body, a, None, length=7)[0]
x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
sh = NamedSharding(mesh, P("x", None))
with mesh:
    c = jax.jit(g, in_shardings=sh, out_shardings=sh).lower(x).compile()
r = H.analyze(c.as_text())
# scalar all-reduce (4 bytes) x 7 trips
assert r.collective_bytes.get("all-reduce") == 28.0, r.collective_bytes
# per-device flops: 7 matmuls of (64,256)@(256,256)
assert r.flops == 7 * 2 * 64 * 256 * 256, r.flops
print("OK")
""", devices=4)


def test_max_collective_elems_reads_tuple_results():
    # XLA prints tuple results with /*index=N*/ comments between elements;
    # the -done half of an async pair repeats its -start and is skipped
    hlo = """HloModule m

ENTRY %main (p: f32[8,64]) -> f32[64] {
  %p = f32[8,64]{1,0} parameter(0)
  %ag = f32[4,8,64]{2,1,0} all-gather-done(f32[4,8,64]{2,1,0} %x)
  %ar = (f32[64]{0}, s32[8]{0}, f32[2]{0}, f32[2]{0}, f32[2]{0}, /*index=5*/f32[3,100]{1,0}) all-reduce-start(%a, %b), to_apply=%add
  ROOT %r = f32[64]{0} all-reduce(f32[64]{0} %y), to_apply=%add
}
"""
    assert H.max_collective_elems(hlo) == 300


def test_sliced_fusion_not_charged_full_buffer():
    # gathering 2 rows from a big table must not count the whole table
    table = jax.ShapeDtypeStruct((4096, 512), jnp.float32)
    idx = jax.ShapeDtypeStruct((2,), jnp.int32)

    def g(t, i):
        return t[i] * 2.0

    r = _analyze(g, table, idx)
    assert r.bytes_accessed < 4096 * 512 * 4 / 4, r.bytes_accessed
