"""Per-kernel shape/dtype sweeps asserting allclose against the ref.py
pure-jnp oracles (interpret mode executes the kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import compress, ops, ref, uplink


# --------------------------------------------------------------------------
# compress
# --------------------------------------------------------------------------


@pytest.mark.parametrize("d", [64, 1000, 4096, 5001])
@pytest.mark.parametrize("c,s", [(8, 3), (16, 4), (12, 2)])
def test_compress_sweep(d, c, s):
    x = jax.random.normal(jax.random.key(d + c), (d,))
    for slot in [0, c // 2, c - 1, c, c + 3]:
        out = ops.compress(x, jnp.asarray([slot], jnp.int32), c, s, block=512)
        exp = ref.compress_ref(x, jnp.asarray(slot, jnp.int32), c, s)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_compress_covers_each_coordinate_s_times():
    d, c, s = 257, 8, 3
    x = jnp.ones((d,))
    total = sum(
        np.asarray(
            ops.compress(x, jnp.asarray([j], jnp.int32), c, s, block=128)
        )
        for j in range(c)
    )
    np.testing.assert_array_equal(total, np.full(d, s))


@given(
    st.integers(2, 20), st.integers(2, 20), st.integers(1, 600),
    st.integers(0, 2**16),
)
@settings(max_examples=25, deadline=None)
def test_compress_property(c, s, d, seed):
    if s > c:
        s = c
    x = jax.random.normal(jax.random.key(seed), (d,))
    slot = seed % (c + 2)
    out = ops.compress(x, jnp.asarray([slot], jnp.int32), c, s, block=128)
    exp = ref.compress_ref(x, jnp.asarray(slot, jnp.int32), c, s)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


@pytest.mark.parametrize("n,d", [(4, 257), (8, 1024), (3, 4097)])
def test_compress_2d_matches_per_row(n, d):
    """The (n, d) form with a grid over clients equals n 1-D calls."""
    x = jax.random.normal(jax.random.key(n * d), (n, d))
    c, s = 8, 3
    slots = jnp.asarray([(3 * i) % (c + 2) for i in range(n)], jnp.int32)
    out = ops.compress(x, slots, c, s, block=128)
    for i in range(n):
        exp = ref.compress_ref(x[i], slots[i], c, s)
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(exp))


# --------------------------------------------------------------------------
# uplink kernels (the fused comm step, DESIGN.md §9): interpret smokes
# --------------------------------------------------------------------------


def _uplink_operands(n, d, m, seed):
    ks = jax.random.split(jax.random.key(seed), 2)
    x = jax.random.normal(ks[0], (n, d), jnp.float32)
    h = jax.random.normal(ks[1], (n, d), jnp.float32)
    rng = np.random.default_rng(seed)
    slot = np.full((n,), -1, np.int32)
    active = rng.choice(n, size=min(m, n), replace=False)
    slot[active] = rng.permutation(min(m, n))
    band = rng.integers(0, m, size=d).astype(np.int32)
    return x, h, jnp.asarray(slot), jnp.asarray(band)


@pytest.mark.parametrize("n,d,m,s", [
    (4, 257, 3, 2),     # ragged d, idle clients
    (8, 1024, 8, 8),    # s == m (no compression), exact block tiling
    (6, 4097, 5, 2),    # multi-block + ragged tail
])
def test_uplink_masked_sum_sweep(n, d, m, s):
    x, _, slot, band = _uplink_operands(n, d, m, n * d)
    out = ops.uplink_masked_sum(x, slot, band, m, s, block=256)
    exp = ref.uplink_masked_sum_ref(x, slot, band, m, s)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(exp), rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("n,d,m,s", [
    (4, 257, 3, 2),
    (8, 1024, 8, 8),
    (6, 4097, 5, 2),
])
def test_uplink_h_update_sweep(n, d, m, s):
    x, h, slot, band = _uplink_operands(n, d, m, n + d)
    x_bar = ref.uplink_masked_sum_ref(x, slot, band, m, s)
    h_new, x_new = ops.uplink_h_update(
        x, h, x_bar, slot, band, m, s, 0.25, block=256
    )
    h_exp, x_exp = ref.uplink_h_update_ref(x, h, x_bar, slot, band, m, s,
                                           0.25)
    np.testing.assert_allclose(
        np.asarray(h_new), np.asarray(h_exp), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(x_new), np.asarray(x_exp))


@pytest.mark.parametrize("n,d,m,s", [
    (4, 257, 3, 2),
    (6, 4097, 5, 2),
])
def test_uplink_h_update_down_mask(n, d, m, s):
    """The DownCom row mask (elastic PP): masked rows get x_bar, the rest
    keep x bit-exactly, h-update unaffected."""
    x, h, slot, band = _uplink_operands(n, d, m, 3 * n + d)
    rng = np.random.default_rng(d)
    down = jnp.asarray(rng.integers(0, 2, size=n).astype(np.int32))
    x_bar = ref.uplink_masked_sum_ref(x, slot, band, m, s)
    h_new, x_new = ops.uplink_h_update(
        x, h, x_bar, slot, band, m, s, 0.25, down=down, block=256
    )
    h_exp, x_exp = ref.uplink_h_update_ref(x, h, x_bar, slot, band, m, s,
                                           0.25, down=down)
    np.testing.assert_allclose(
        np.asarray(h_new), np.asarray(h_exp), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(x_new), np.asarray(x_exp))
    dn = np.asarray(down).astype(bool)
    np.testing.assert_array_equal(np.asarray(x_new)[~dn],
                                  np.asarray(x)[~dn])
    np.testing.assert_array_equal(
        np.asarray(x_new)[dn],
        np.broadcast_to(np.asarray(x_bar), (int(dn.sum()), d)),
    )


@given(
    st.integers(2, 10), st.integers(2, 12), st.integers(2, 12),
    st.integers(1, 700), st.integers(0, 2**16),
)
@settings(max_examples=20, deadline=None)
def test_uplink_kernels_property(n, m, s, d, seed):
    if s > m:
        s = m
    x, h, slot, band = _uplink_operands(n, d, m, seed)
    x_bar = ops.uplink_masked_sum(x, slot, band, m, s, block=128)
    np.testing.assert_allclose(
        np.asarray(x_bar),
        np.asarray(ref.uplink_masked_sum_ref(x, slot, band, m, s)),
        rtol=1e-6, atol=1e-6,
    )
    h_new, x_new = ops.uplink_h_update(
        x, h, x_bar, slot, band, m, s, 0.5, block=128
    )
    h_exp, x_exp = ref.uplink_h_update_ref(
        x, h, x_bar, slot, band, m, s, 0.5
    )
    np.testing.assert_allclose(
        np.asarray(h_new), np.asarray(h_exp), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(x_new), np.asarray(x_exp))


@pytest.mark.parametrize("kind,k", [("trimmed", 1), ("median", 0)])
@pytest.mark.parametrize("n,d,m,s", [
    (8, 3000, 6, 3),    # two idle rows, ragged tail
    (12, 1500, 12, 5),  # full participation
])
def test_uplink_robust_sum_matches_rank_ref(kind, k, n, d, m, s):
    """Ties included (values rounded to halves): the kernel's first-row
    tie break and the oracle's row-index ranks pick the same values."""
    x, _, slot, band = _uplink_operands(n, d, m, 7 * n + d)
    x = jnp.round(x * 2) / 2
    bar, cnt = uplink.robust_sum(x, slot, band, m, s, kind=kind, k=k,
                                 block=1024, interpret=True)
    bar_exp, cnt_exp = ref.uplink_robust_sum_ref(x, slot, band, m, s,
                                                 kind, k)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_exp))
    np.testing.assert_allclose(np.asarray(bar), np.asarray(bar_exp),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("counts", [False, True])
def test_uplink_masked_sum_of_int_wire_matches_ref(counts):
    """The int-wire UpCom as the pallas comm runs it: codes through the
    shared dequant, then the float kernel — against the oracle dequant."""
    n, d, m, s = 6, 4097, 5, 2
    _, _, slot, band = _uplink_operands(n, d, m, d)
    rng = np.random.default_rng(d)
    codes = jnp.asarray(rng.integers(-127, 128, (n, d)), jnp.int8)
    scales = jnp.asarray(rng.random((n, -(-d // 256))), jnp.float32)
    chunk = jnp.arange(d, dtype=jnp.int32) // 256
    vals = compress.wire_dequant(codes, scales, chunk)
    np.testing.assert_array_equal(
        np.asarray(vals), np.asarray(ref.wire_dequant_ref(codes, scales)))
    out = uplink.masked_sum(vals, slot, band, m, s, counts=counts,
                            block=1024, interpret=True)
    exp = ref.uplink_masked_sum_ref(ref.wire_dequant_ref(codes, scales),
                                    slot, band, m, s, counts=counts)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(exp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_uplink_h_update_covered_keeps_uncovered_coords():
    n, d, m, s = 6, 4097, 5, 2
    x, h, slot, band = _uplink_operands(n, d, m, 5 * n + d)
    x_bar = ref.uplink_masked_sum_ref(x, slot, band, m, s)
    cov = jnp.asarray(np.random.default_rng(1).random(d) < 0.7)
    down = jnp.asarray([1, 0, 1, 1, 0, 1], jnp.int32)
    h_new, x_new = uplink.h_update(x, h, x_bar, slot, band, m, s, 0.25,
                                   down=down, covered=cov, block=1024,
                                   interpret=True)
    h_exp, x_exp = ref.uplink_h_update_ref(x, h, x_bar, slot, band, m, s,
                                           0.25, down=down, covered=cov)
    np.testing.assert_allclose(np.asarray(h_new), np.asarray(h_exp),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(x_new), np.asarray(x_exp))
    unc = ~np.asarray(cov)
    np.testing.assert_array_equal(np.asarray(x_new)[:, unc],
                                  np.asarray(x)[:, unc])
    np.testing.assert_array_equal(np.asarray(h_new)[:, unc],
                                  np.asarray(h)[:, unc])


@pytest.mark.parametrize("n,d,itemsizes", [
    (8, 36_501_504, [4]), (512, 1 << 20, [4, 4, 4, 4]), (8, 300, [1, 4]),
])
def test_fit_block_is_a_whole_vector_tile_within_vmem(n, d, itemsizes):
    blk = compress.fit_block(4096, d, n, itemsizes)
    if blk == d:
        return
    assert blk % compress.VEC_TILE == 0 and 0 < blk <= 4096
    rows = -(-n // 8) * 8
    tiles = rows * blk * (2 * sum(itemsizes) + 8)
    assert blk == compress.VEC_TILE or tiles <= compress.VMEM_TILE_BYTES


# --------------------------------------------------------------------------
# leaf-native uplink kernels: one state leaf in its own (n, R, C) view,
# ownership from the leaf-local coordinate index (no band table)
# --------------------------------------------------------------------------


def _closed_form_band(template, D, m, s):
    from repro.dist import comm_ws

    if template == "cyclic":
        return jnp.asarray(comm_ws._cyclic_band_np((D,), m, s))
    return jnp.asarray(comm_ws._block_leaf_band_np(D, m))


@pytest.mark.parametrize("template", ["cyclic", "blocked"])
@pytest.mark.parametrize("trail", [
    (3000,),       # a 1-D parameter: (n, 1, C), C ragged against c_blk
    (37, 300),     # R ragged against r_blk, C not a multiple of 128
    (3, 20, 200),  # 4-D stacked leaf folded to (n, 60, 200)
])
@pytest.mark.parametrize("variant", ["all_rows", "down", "survivor"])
def test_leaf_uplink_matches_ref(template, trail, variant):
    """The leaf kernels over a small ``block`` (a multi-step 2-D grid with
    ragged last blocks) against the ref.py oracles fed the closed-form
    band of the flattened leaf: c < n (idle rows), the DownCom row mask,
    and the survivor ``counts`` / ``covered`` variants with dropped
    owners."""
    n, m, s = 6, 5, 2
    rng = np.random.default_rng(len(trail) * 7 + len(variant))
    x = jnp.asarray(rng.normal(size=(n,) + trail), jnp.float32)
    h = jnp.asarray(rng.normal(size=(n,) + trail), jnp.float32)
    slot = np.full((n,), -1, np.int32)
    slot[rng.choice(n, size=m, replace=False)] = rng.permutation(m)
    if variant == "survivor":
        # drop the rows holding slots 0 and 1: both templates give every
        # coordinate s=2 owners at consecutive slots, so some lose both
        slot[(slot == 0) | (slot == 1)] = -1
    slot = jnp.asarray(slot)
    R, C = uplink.leaf_view(trail)
    D = R * C
    x3, h3 = x.reshape(n, R, C), h.reshape(n, R, C)
    xf, hf = x.reshape(n, D), h.reshape(n, D)
    band = _closed_form_band(template, D, m, s)
    kw = dict(template=template, block=1024, interpret=True)
    down = cov = None
    if variant == "survivor":
        num, cnt = uplink.leaf_masked_sum(x3, slot, m, s, counts=True, **kw)
        num_e, cnt_e = ref.uplink_masked_sum_ref(xf, slot, band, m, s,
                                                 counts=True)
        np.testing.assert_array_equal(np.asarray(cnt).reshape(-1),
                                      np.asarray(cnt_e))
        np.testing.assert_allclose(np.asarray(num).reshape(-1),
                                   np.asarray(num_e), rtol=1e-6, atol=1e-6)
        x_bar, cov = num / jnp.maximum(cnt, 1.0), cnt > 0
        assert not bool(cov.all())  # the dropped owners uncover some
    else:
        x_bar = uplink.leaf_masked_sum(x3, slot, m, s, **kw)
        np.testing.assert_allclose(
            np.asarray(x_bar).reshape(-1),
            np.asarray(ref.uplink_masked_sum_ref(xf, slot, band, m, s)),
            rtol=1e-6, atol=1e-6)
    if variant != "all_rows":
        down = jnp.asarray(rng.integers(0, 2, size=n).astype(np.int32))
    h_new, x_new = uplink.leaf_h_update(x3, h3, x_bar, slot, m, s, 0.25,
                                        down=down, covered=cov, **kw)
    h_exp, x_exp = ref.uplink_h_update_ref(
        xf, hf, x_bar.reshape(-1), slot, band, m, s, 0.25, down=down,
        covered=None if cov is None else cov.reshape(-1))
    assert h_new.shape == x_new.shape == (n, R, C)
    np.testing.assert_allclose(np.asarray(h_new).reshape(n, D),
                               np.asarray(h_exp), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(x_new).reshape(n, D),
                                  np.asarray(x_exp))


@pytest.mark.parametrize("n,R,C,itemsizes", [
    (4, 2560, 6912, [4, 4, 4, 4]),   # stablelm's MLP leaves, h_update
    (8, 51968, 384, [4]),            # whisper's padded embedding, masked_sum
    (8, 1, 19_955_712, [2, 4] * 2),  # a 1-D parameter, bf16 x
    (512, 1536, 384, [4, 4, 4, 4]),  # many rows a shard
])
def test_fit_tile_is_tiled_and_within_vmem(n, R, C, itemsizes):
    r_blk, c_blk = compress.fit_tile(R, C, n, itemsizes, [4])
    sub = 32 // min(itemsizes)
    assert c_blk == C or (c_blk % 128 == 0 and c_blk < C)
    assert r_blk == R or (r_blk % sub == 0 and r_blk < R)
    per = n * 2 * sum(itemsizes) + 2 * 4 + 4 * 6
    assert r_blk * c_blk <= compress.LEAF_BLOCK or r_blk * c_blk <= 8 * 128
    assert (r_blk * c_blk * per <= compress.VMEM_TILE_BYTES
            or (r_blk <= sub and c_blk == 128))


# --------------------------------------------------------------------------
# fused local step
# --------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(64,), (33, 7), (4, 5, 6)])
def test_local_step_sweep(dtype, shape):
    ks = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
    g = jax.random.normal(ks[1], shape, jnp.float32)
    h = jax.random.normal(ks[2], shape, jnp.float32)
    out = ops.fused_local_step(x, g, h, 0.03, block=128)
    exp = ref.fused_local_step_ref(x, g, h, 0.03)
    assert out.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        rtol=1e-6, atol=1e-6,
    )


def test_local_step_interpret_auto_detects_backend():
    """The raw kernel's default is now per-backend auto-detection (the
    seed hard-coded ``interpret=True``, which would have silently run the
    interpreter on real TPUs): ``None`` resolves via the shared
    ``compress.resolve_interpret`` policy, and the auto path is
    bit-identical to forced interpret mode off-TPU."""
    from repro.kernels import local_step
    from repro.kernels.compress import resolve_interpret

    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    ks = jax.random.split(jax.random.key(7), 3)
    x = jax.random.normal(ks[0], (1000,), jnp.float32).astype(jnp.bfloat16)
    g = jax.random.normal(ks[1], (1000,))
    h = jax.random.normal(ks[2], (1000,))
    auto = local_step.fused_local_step(x, g, h, 0.07, block=256)
    forced = local_step.fused_local_step(
        x, g, h, 0.07, block=256, interpret=True
    )
    assert auto.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(auto, np.float32), np.asarray(forced, np.float32)
    )


@given(st.integers(1, 3000), st.floats(1e-4, 1.0), st.integers(0, 2**16))
@settings(max_examples=25, deadline=None)
def test_local_step_property(d, gamma, seed):
    ks = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(ks[0], (d,))
    g = jax.random.normal(ks[1], (d,))
    h = jax.random.normal(ks[2], (d,))
    out = ops.fused_local_step(x, g, h, gamma, block=256)
    exp = ref.fused_local_step_ref(x, g, h, gamma)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(exp), rtol=1e-5, atol=1e-6
    )


# --------------------------------------------------------------------------
# decode attention
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,h,kvh,hd,S,bs",
    [
        (2, 8, 4, 64, 1024, 256),
        (1, 4, 1, 128, 2048, 512),
        (3, 6, 6, 32, 512, 128),   # MHA (whisper-like)
        (1, 8, 1, 64, 1024, 1024),  # single KV block
    ],
)
def test_decode_attention_sweep(b, h, kvh, hd, S, bs):
    ks = jax.random.split(jax.random.key(b * h + S), 3)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, S, kvh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, S, kvh, hd), jnp.float32)
    for pos in [0, S // 3, S - 1]:
        out = ops.decode_attention(
            q, k, v, jnp.asarray(pos, jnp.int32), block_s=bs
        )
        exp = ref.decode_attention_ref(q, k, v, jnp.asarray(pos, jnp.int32))
        assert float(jnp.abs(out - exp).max()) < 2e-5, pos


@pytest.mark.parametrize("window", [16, 128])
@pytest.mark.parametrize("softcap", [None, 30.0])
def test_decode_attention_window_softcap(window, softcap):
    b, h, kvh, hd, S = 2, 4, 2, 64, 512
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, S, kvh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, S, kvh, hd), jnp.float32)
    pos = jnp.asarray(300, jnp.int32)
    out = ops.decode_attention(
        q, k, v, pos, window=window, softcap=softcap, block_s=128
    )
    exp = ref.decode_attention_ref(q, k, v, pos, window=window,
                                   softcap=softcap)
    assert float(jnp.abs(out - exp).max()) < 2e-5


def test_decode_attention_bf16():
    b, h, kvh, hd, S = 1, 4, 2, 64, 512
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, S, kvh, hd), jnp.float32).astype(
        jnp.bfloat16
    )
    v = jax.random.normal(ks[2], (b, S, kvh, hd), jnp.float32).astype(
        jnp.bfloat16
    )
    pos = jnp.asarray(S - 1, jnp.int32)
    out = ops.decode_attention(q, k, v, pos, block_s=128)
    exp = ref.decode_attention_ref(q, k, v, pos)
    assert out.dtype == jnp.bfloat16
    err = float(jnp.abs(
        out.astype(jnp.float32) - exp.astype(jnp.float32)
    ).max())
    assert err < 3e-2, err
