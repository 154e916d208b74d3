"""Fused round engine tests: equivalence with the per-step path, compile
cache bound, on-device data determinism, checkpoint round-trip mid-run."""

import pytest


def test_fused_round_equals_per_step(subproc):
    """One engine round matches (<=1e-6) L per-step local_step calls +
    comm_step replayed on the same key schedule — for both uplinks
    (block_rs now at c < n too) and local_opt='adamw', at L spanning
    single- and multi-chunk buckets — and the compile cache stays within
    log2(max_L)+1.  At c < n the replay runs the ELASTIC semantics: gather
    the device-derived cohort, train the compact state on cohort-only
    batches, scatter, comm with the cohort and next-cohort DownCom."""
    subproc("""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.transformer import ModelConfig
from repro.data import DataConfig, device_sampler
from repro.data.pipeline import SyntheticTokenPipeline
from repro.dist import rounds, sharding, tamuna_dp

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
cfg = ModelConfig(family="dense", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab=128, dtype=jnp.float32,
                  remat=False)
n = sharding.n_clients(mesh)
dcfg = DataConfig(seq_len=16, per_client_batch=2, vocab=64, seed=0,
                  n_clients=n)
pipe = SyntheticTokenPipeline(dcfg, cfg, mesh)
data = pipe.device_data()
sampler = device_sampler(dcfg, cfg, mesh)

for uplink, opt in [("masked_psum", "sgd"), ("block_rs", "sgd"),
                    ("masked_psum", "adamw")]:
    c = 3
    tcfg = tamuna_dp.DistTamunaConfig(gamma=0.05, c=c, s=2, p=0.5,
                                      uplink=uplink, local_opt=opt)
    def mk_state():
        st = tamuna_dp.init_state(jax.random.key(0), cfg, mesh, tcfg)
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          tamuna_dp.state_pspecs(st, cfg, mesh),
                          is_leaf=lambda x: isinstance(x, P))
        return jax.device_put(st, sh)

    # elastic forced: this 4x2 host mesh has one client per data shard,
    # where the default keeps the all-rows body (the gather cannot vacate
    # hardware there) — the replay below tests the elastic semantics
    round_fn = rounds.make_round_fn(cfg, tcfg, mesh, sample_batch=sampler,
                                    max_L=8, elastic=True)
    assert round_fn.elastic and round_fn.c == c and round_fn.n == n
    local = jax.jit(tamuna_dp.make_local_step(cfg, tcfg))
    comm = jax.jit(tamuna_dp.make_comm_step(cfg, tcfg, mesh))

    # L=1: single bucket; L=3: two chunks (2+1); L=5: two chunks (4+1)
    for L in (1, 3, 5):
        carry = rounds.init_carry(mk_state(), jax.random.key(7),
                                  flush_every=1)
        # snapshot the base keys BEFORE the engine donates the carry
        dk = np.asarray(carry.data_key).copy()
        ck = np.asarray(carry.comm_key).copy()

        # per-step reference on the SAME key schedule and cohort plan
        ref = mk_state()
        cohort = tamuna_dp.round_cohort(
            rounds.comm_round_key(ck, ref.round), n, c)
        down = tamuna_dp.member_mask(
            tamuna_dp.round_cohort(
                rounds.comm_round_key(ck, ref.round + 1), n, c), n)
        work = tamuna_dp.gather_cohort(ref, cohort)
        acc = 0.0
        for t in range(L):
            batch = sampler(data, rounds.data_step_key(dk, t),
                            clients=cohort)
            work, m = local(work, **batch)
            acc += float(m["loss"])
        ref = tamuna_dp.scatter_cohort(ref, work, cohort)
        ckey = rounds.comm_round_key(ck, ref.round)
        ref = comm(ref, jax.random.key_data(ckey), cohort=cohort,
                   down=down)

        carry = round_fn(carry, data, L, 0)

        # states match to <= 1e-6 on every leaf (x, h, opt)
        for name, a, b in [("x", carry.state.x, ref.x),
                           ("h", carry.state.h, ref.h),
                           ("opt", carry.state.opt, ref.opt)]:
            errs = jax.tree.map(
                lambda u, v: float(jnp.max(jnp.abs(
                    u.astype(jnp.float32) - v.astype(jnp.float32)))), a, b)
            err = max(jax.tree.leaves(errs), default=0.0)
            assert err <= 1e-6, (uplink, opt, L, name, err)
        assert int(carry.state.round) == int(ref.round) == 1
        assert int(carry.t) == L
        # device traces match the per-step loss sum and counters
        tr = jax.device_get(carry.traces)
        np.testing.assert_allclose(tr["loss_sum"][0], acc, rtol=1e-5)
        assert int(tr["steps"][0]) == L
        assert float(tr["up_floats"][0]) == float(ref.up_floats)
    # compile cache bound: chunks of {1,3,5} are {1,2,4} -> <= log2(8)+1
    assert len(round_fn.cache) <= 4, sorted(round_fn.cache)
print("OK")
""", timeout=1500)


def test_compile_cache_bounded_over_geometric_rounds(subproc):
    """30 geometric rounds compile at most log2(max_L)+1 distinct programs."""
    subproc("""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.transformer import ModelConfig
from repro.data import DataConfig, device_sampler
from repro.data.pipeline import SyntheticTokenPipeline
from repro.dist import rounds, sharding, tamuna_dp

mesh = jax.make_mesh((4, 1), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
cfg = ModelConfig(family="dense", n_layers=1, d_model=32, n_heads=2,
                  n_kv_heads=2, d_ff=64, vocab=64, dtype=jnp.float32,
                  remat=False)
n = sharding.n_clients(mesh)
dcfg = DataConfig(seq_len=8, per_client_batch=1, vocab=64, seed=0,
                  n_clients=n)
pipe = SyntheticTokenPipeline(dcfg, cfg, mesh)
tcfg = tamuna_dp.DistTamunaConfig(gamma=0.05, c=3, s=2, p=0.34)
state = tamuna_dp.init_state(jax.random.key(0), cfg, mesh, tcfg)
sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                  tamuna_dp.state_pspecs(state, cfg, mesh),
                  is_leaf=lambda x: isinstance(x, P))
state = jax.device_put(state, sh)
MAX_L = 16
round_fn = rounds.make_round_fn(
    cfg, tcfg, mesh, sample_batch=device_sampler(dcfg, cfg, mesh),
    max_L=MAX_L)
rng = np.random.default_rng(0)
seen = set()
data = pipe.device_data()
carry = rounds.init_carry(state, jax.random.key(1), 8)
for r in range(30):
    L = tamuna_dp.sample_round_length(rng, tcfg.p, max_L=MAX_L)
    seen.add(L)
    carry = round_fn(carry, data, L, r % 8)
assert len(seen) > 4, seen  # geometric draws actually varied
assert len(round_fn.cache) <= 5, sorted(round_fn.cache)  # log2(16)+1
# chunk decomposition is exact for every length
for L in range(1, MAX_L + 1):
    assert sum(rounds.round_chunks(L, MAX_L)) == L
assert sum(rounds.round_chunks(100, MAX_L)) == MAX_L  # cap
print("OK")
""", devices=4, timeout=1500)


def test_lowered_rebuilds_every_program_that_ran(subproc):
    """round_fn.lowered() hands back one lowering per compiled bucket, from
    the shapes and shardings the programs ran with (uncommitted scalars
    included) — on a dp-sharded mesh, where pinning them fails."""
    subproc("""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.transformer import ModelConfig
from repro.data import DataConfig, device_sampler
from repro.data.pipeline import SyntheticTokenPipeline
from repro.dist import rounds, sharding, tamuna_dp

mesh = jax.make_mesh((4, 1), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
cfg = ModelConfig(family="dense", n_layers=1, d_model=32, n_heads=2,
                  n_kv_heads=2, d_ff=64, vocab=64, dtype=jnp.float32,
                  remat=False)
n = sharding.n_clients(mesh)
dcfg = DataConfig(seq_len=8, per_client_batch=1, vocab=64, seed=0,
                  n_clients=n)
pipe = SyntheticTokenPipeline(dcfg, cfg, mesh)
tcfg = tamuna_dp.DistTamunaConfig(gamma=0.05, c=3, s=2, p=0.34,
                                  comm_impl="pallas")
state = tamuna_dp.init_state(jax.random.key(0), cfg, mesh, tcfg)
sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                  tamuna_dp.state_pspecs(state, cfg, mesh),
                  is_leaf=lambda x: isinstance(x, P))
state = jax.device_put(state, sh)
round_fn = rounds.make_round_fn(
    cfg, tcfg, mesh, sample_batch=device_sampler(dcfg, cfg, mesh), max_L=4)
data = pipe.device_data()
carry = rounds.init_carry(state, jax.random.key(1), 8)
# traced calls (an outer jit or eval_shape) record nothing
jax.eval_shape(lambda cr: round_fn(cr, data, 1, 0), carry)
assert round_fn.lowered() == {}
for r, L in enumerate((1, 3, 2)):
    carry = round_fn(carry, data, L, r)
low = round_fn.lowered()
assert set(low) == set(round_fn.cache) and len(low) == 2, sorted(low)
for key, lw in low.items():
    text = lw.as_text()
    assert "sdy.sharding" in text or "mhlo.sharding" in text, key
    lw.compile()
print("OK")
""", devices=4, timeout=900)


def test_run_rounds_checkpoint_roundtrip_bf16_adamw(subproc):
    """DistTamunaState (bf16 params + AdamW moments) survives
    checkpoint.save/restore mid-run from run_rounds, bit-exactly, and the
    restored state continues training."""
    subproc("""
import os, tempfile
import numpy as np
import jax, jax.numpy as jnp
import ml_dtypes
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import checkpoint
from repro.models.transformer import ModelConfig
from repro.data import DataConfig, device_sampler
from repro.data.pipeline import SyntheticTokenPipeline
from repro.dist import rounds, sharding, tamuna_dp

mesh = jax.make_mesh((4, 1), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
cfg = ModelConfig(family="dense", n_layers=1, d_model=32, n_heads=2,
                  n_kv_heads=2, d_ff=64, vocab=64, dtype=jnp.float32,
                  param_dtype=jnp.bfloat16, remat=False)
n = sharding.n_clients(mesh)
dcfg = DataConfig(seq_len=8, per_client_batch=1, vocab=64, seed=0,
                  n_clients=n)
pipe = SyntheticTokenPipeline(dcfg, cfg, mesh)
tcfg = tamuna_dp.DistTamunaConfig(gamma=0.01, c=3, s=2, p=0.5,
                                  local_opt="adamw")
state = tamuna_dp.init_state(jax.random.key(0), cfg, mesh, tcfg)
sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                  tamuna_dp.state_pspecs(state, cfg, mesh),
                  is_leaf=lambda x: isinstance(x, P))
state = jax.device_put(state, sh)
assert any(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(state.x))
round_fn = rounds.make_round_fn(
    cfg, tcfg, mesh, sample_batch=device_sampler(dcfg, cfg, mesh), max_L=4)
d = tempfile.mkdtemp()
final, last = rounds.run_rounds(
    state, round_fn=round_fn, data=pipe.device_data(),
    key=jax.random.key(3), rounds=2, rng=np.random.default_rng(0),
    p=tcfg.p, flush_every=2, checkpoint_dir=d, checkpoint_every=2)
assert os.path.isdir(os.path.join(d, "step_2"))
assert last["round"] == 1 and last["local_steps"] >= 2

like = jax.tree.map(jnp.zeros_like, final)
restored = checkpoint.restore(os.path.join(d, "step_2"), like)
for a, b in zip(jax.tree.leaves(final), jax.tree.leaves(restored)):
    assert a.dtype == b.dtype, (a.dtype, b.dtype)
    an, bn = np.asarray(a), np.asarray(b)
    if a.dtype == jnp.bfloat16:  # bit-exact bf16 round-trip
        np.testing.assert_array_equal(an.view(np.uint16),
                                      bn.view(np.uint16))
    else:
        np.testing.assert_array_equal(an, bn)

# the restored state continues training through the engine
restored = jax.device_put(restored, sh)
cont, last2 = rounds.run_rounds(
    restored, round_fn=round_fn, data=pipe.device_data(),
    key=jax.random.key(4), rounds=1, rng=np.random.default_rng(1),
    p=tcfg.p, flush_every=1)
assert int(cont.round) == 3  # 2 checkpointed rounds + 1 continued
assert np.isfinite(last2["loss"])
print("OK")
""", devices=4, timeout=1500)


def test_device_sampler_matches_engine_schedule(subproc):
    """The on-device sampler is pure: same key -> same batch, eager or
    jitted, and tokens land in [0, vocab)."""
    subproc("""
import numpy as np
import jax, jax.numpy as jnp
from repro.models.transformer import ModelConfig
from repro.data import DataConfig, device_sample_batch
from repro.data.pipeline import SyntheticTokenPipeline

cfg = ModelConfig(family="dense", n_layers=1, d_model=32, n_heads=2,
                  n_kv_heads=2, d_ff=64, vocab=64, dtype=jnp.float32,
                  remat=False)
dcfg = DataConfig(seq_len=12, per_client_batch=3, vocab=64, seed=5,
                  n_clients=4)
pipe = SyntheticTokenPipeline(dcfg, cfg)
data = pipe.device_data()
key = jax.random.key(9)
b1 = device_sample_batch(data, key, dcfg=dcfg, model_cfg=cfg)
b2 = jax.jit(lambda d, k: device_sample_batch(d, k, dcfg=dcfg,
                                              model_cfg=cfg))(data, key)
for k in b1:
    np.testing.assert_array_equal(np.asarray(b1[k]), np.asarray(b2[k]))
assert b1["tokens"].shape == (4, 3, 12)
assert int(b1["tokens"].min()) >= 0 and int(b1["tokens"].max()) < 64
# labels are the next-token shift of the same chain
np.testing.assert_array_equal(np.asarray(b1["tokens"][..., 1:]),
                              np.asarray(b1["labels"][..., :-1]))
print("OK")
""", devices=1, timeout=900)
