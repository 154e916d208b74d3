"""The one traffic generator: every workload file is read through here.

A training cell's traffic is the data its clients train on and the
lengths of its rounds:

* ``LengthSchedule`` deals round lengths from a fixed block (the
  workload's ``round_lengths``: how many rounds of each length), shuffled
  by the seed, so every seed does the same work in another order.  It
  stands in for ``run_rounds``'s host ``rng``: it calls its
  ``geometric``.
* ``bigram_tables`` makes each client's Markov chain over ``token_ids``
  ids on the device (one jitted call): the per-client heterogeneous
  bigram data of the trainer's synthetic pipeline.
* ``sample_batch`` draws one local step's batch on the device from those
  tables, keyed by client id (a copy of the trainer's device sampler, so
  the benchmark's inputs do not move when the program's pipeline does).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


def seed_sequence(seed: int) -> np.random.SeedSequence:
    """Every random draw of a run starts here: any whole number, however
    large."""
    return np.random.SeedSequence(int(seed) % (1 << 64))


def seed_key(seed: int, stream: int) -> jax.Array:
    """A JAX key for one named stream of the run (weights, tables, ...)."""
    word = seed_sequence(seed).spawn(stream + 1)[stream].generate_state(1)
    return jax.random.key(int(word[0]))


def key_data(seed: int, stream: int, count: int = 0):
    """Raw ``uint32`` key data on the host: one key (``count`` 0) or
    ``count`` keys of one named stream."""
    words = seed_sequence(seed).spawn(stream + 1)[stream].generate_state(
        2 * max(count, 1))
    return words.reshape(-1, 2) if count else words


class LengthSchedule:
    """Round lengths dealt from a shuffled fixed block.

    ``counts`` maps a round length to how many rounds of each block have
    it; the block's mean is the cell's E[L].  ``geometric(p)`` has the
    signature of ``numpy.random.Generator.geometric`` so ``run_rounds`` takes
    it for its host ``rng``; ``p`` is ignored, the block already holds the
    distribution."""

    def __init__(self, counts: Dict[int, int], seed: int):
        self.block = [int(L) for L, k in sorted(counts.items(),
                                                key=lambda kv: int(kv[0]))
                      for _ in range(int(k))]
        self._rng = np.random.default_rng(seed_sequence(seed).spawn(8)[7])
        self._queue: list = []

    def geometric(self, p: float) -> int:
        del p
        if not self._queue:
            self._queue = [int(L) for L in self._rng.permutation(self.block)]
        return self._queue.pop()


def bigram_tables(key: jax.Array, n: int, v: int) -> Dict[str, jax.Array]:
    """Per-client cumulative transition tables ``{"cum": (n, v, v) f32}``:
    row ``a`` of client ``i`` is the cumulative distribution of the id
    that follows ``a``, from logits ~ N(0, 2^2), distinct per client."""
    logits = jax.random.normal(key, (n, v, v), jnp.float32) * 2.0
    return {"cum": jnp.cumsum(jax.nn.softmax(logits, axis=-1), axis=-1)}


def sample_batch(data: Dict[str, jax.Array], key: jax.Array, *,
                 seq_len: int, batch: int, frames: int = 0, d_model: int = 0,
                 frame_dtype=jnp.bfloat16, mesh=None, dp_spec=None,
                 clients: Optional[jax.Array] = None) -> Dict[str, jax.Array]:
    """One ``(rows, batch, ...)`` batch drawn on the device.

    Client ``i``'s chain is keyed by ``fold_in(key, i)``, so a cohort's
    batch (``clients`` given) holds the same rows as the full batch would.
    ``frames`` > 0 adds ``(rows, batch, frames, d_model)`` N(0, 1) frame
    embeddings, the encoder input of an encoder-decoder model."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key_t = key
    else:
        key_t = jax.random.wrap_key_data(key)
    cum = data["cum"]
    n, v = cum.shape[0], cum.shape[-1]
    k_tok, _, k_fr = jax.random.split(key_t, 3)
    cohort = clients is not None
    ids = jnp.arange(n) if clients is None else clients
    cks = jax.vmap(lambda i: jax.random.fold_in(k_tok, i))(ids)
    state0 = jax.vmap(lambda k: jax.random.randint(
        jax.random.fold_in(k, 0), (batch,), 0, v, jnp.int32))(cks)
    rowix = ids[:, None]
    search = jax.vmap(jax.vmap(
        lambda row, u: jnp.searchsorted(row, u, side="right")))

    def step(state, j):
        kj = jax.vmap(lambda k: jax.random.fold_in(k, j))(cks)
        u = jax.vmap(lambda k: jax.random.uniform(k, (batch,)))(kj)
        nxt = jnp.clip(search(cum[rowix, state], u), 0, v - 1)
        return nxt.astype(jnp.int32), state

    # s_0 .. s_T: tokens are s_{:-1}, labels s_{1:}
    _, seq = jax.lax.scan(step, state0, jnp.arange(1, seq_len + 2))
    toks = jnp.moveaxis(seq, 0, -1)
    if mesh is not None and not cohort:
        from jax.sharding import NamedSharding, PartitionSpec as P

        toks = jax.lax.with_sharding_constraint(
            toks, NamedSharding(mesh, P(dp_spec, None, None)))
    out = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
    if frames:
        fks = jax.vmap(lambda i: jax.random.fold_in(k_fr, i))(ids)
        fr = jax.vmap(lambda k: jax.random.normal(
            k, (batch, frames, d_model), jnp.float32))(fks)
        out["frames"] = fr.astype(frame_dtype)
    return out


def sampler(wl: dict, cfg, mesh=None, dp_spec=None):
    """The ``sample_batch(data, key, clients=None)`` callable of a cell."""
    frames = cfg.n_frames if cfg.family == "encdec" else 0
    return partial(sample_batch, seq_len=wl["seq_len"],
                   batch=wl["per_client_batch"], frames=frames,
                   d_model=cfg.d_model, frame_dtype=cfg.dtype, mesh=mesh,
                   dp_spec=dp_spec)
