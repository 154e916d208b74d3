"""The counting functions: each configuration's FLOPs per sample against
XLA's cost analysis of the model's loss and gradient, and the comm
step's algorithmic bytes."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import counting, harness

# matmul-bound sizes (the rehearsal widths are too narrow for the matrix
# products to dominate), one cross-entropy chunk of tokens so no padded
# rows are computed
WIDE = dict(d_model=512, n_heads=4, n_kv_heads=4, d_ff=2048, vocab=4096,
            n_frames=256)
SEQ, BATCH = 512, 2


def compiled_flops(cfg):
    from repro.dist import model_api

    params = jax.eval_shape(lambda: model_api.init(jax.random.key(0), cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32),
             "labels": jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)}
    if cfg.family == "encdec":
        batch["frames"] = jax.ShapeDtypeStruct(
            (BATCH, cfg.n_frames, cfg.d_model), cfg.dtype)
    grad = jax.jit(jax.grad(lambda p, b: model_api.loss(p, cfg, **b)[0]))
    ca = grad.lower(params, batch).compile().cost_analysis()
    return (ca[0] if isinstance(ca, list) else ca)["flops"]


# remat recomputes the forward pass (a third of the total); causal
# attention's masked half and the elementwise work add a few percent
@pytest.mark.parametrize("name", ["whisper_tiny", "stablelm_3b_share"])
@pytest.mark.parametrize("remat,hi", [(False, 1.10), (True, 1.34)])
def test_flops_per_sample_matches_cost_analysis(name, remat, hi):
    cfg = dataclasses.replace(harness.model_config(name, rehearse=True),
                              remat=remat, **WIDE)
    ours = harness.config_module(name).flops_per_sample(cfg, SEQ) * BATCH
    ratio = compiled_flops(cfg) / ours
    assert 1.0 <= ratio <= hi, ratio


def test_comm_bytes_counts_owners_and_receivers():
    # s owners read x (4 B) and read+write h (8 B); c receivers written
    assert counting.comm_bytes([10, 6], n=8, c=6, s=2) == 16 * (8 + 16 + 24)
    # full participation: every row receives xbar
    assert counting.comm_bytes([5], n=4, c=4, s=2) == 5 * (8 + 16 + 16)
