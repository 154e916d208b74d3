"""Plain reference of the first rounds of a cell: TAMUNA (Condat et al.,
arXiv:2302.09832, Algorithm 1) over ``n`` client rows of parameters,
written from the algorithm and not from the program.

A round of length L on the client rows ``x_i``, ``h_i`` (float32):

* local: each cohort client ``i`` takes L steps
  ``x_i <- x_i - gamma * (grad f_i(x_i) - h_i)`` on its own batches;
* UpCom: coordinate ``k`` of a leaf is owned by exactly ``s`` cohort
  clients; ``xbar_k`` is the mean of their ``x_ik``;
* control variates: each owner ``i`` of ``k`` sets
  ``h_ik += (eta / gamma) * (xbar_k - x_ik)``;
* DownCom: the next round's cohort rows (every row under full
  participation) are set to ``xbar``.

Ownership follows the engine's templates over the cohort's slots
(``masked_psum``: the cyclic band, client at slot ``a`` owns ``k`` iff
``(perm[a] - s k) mod c < s``; ``block_rs``: ``c`` contiguous blocks of
``ceil(D / c)``, owned iff ``(block(k) - a - off) mod c < s``).  The
randomness is the engine's documented key schedule (``rounds.py``): from
the round key ``key0`` the data and comm bases are ``split(key0)``; step
``t``'s batch key is ``fold_in(data, t)``; round ``r``'s comm key
``kr = fold_in(comm, r)``; its cohort the sorted
``choice(split(kr)[0], n, c, replace=False)``, its permutation
``permutation(split(kr)[1], c)`` and its block shift
``randint(split(kr)[1], 0, c)``."""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def cohort_of(kr, n: int, c: int):
    k_cohort, _ = jax.random.split(kr)
    return jnp.sort(jax.random.choice(k_cohort, n, shape=(c,),
                                      replace=False)).astype(jnp.int32)


def template_column(kr, c: int, uplink: str):
    """Per cohort slot: its cyclic template column, or the block shift."""
    _, k2 = jax.random.split(kr)
    if uplink == "block_rs":
        return jax.random.randint(k2, (), 0, c, jnp.int32)
    return jax.random.permutation(k2, c).astype(jnp.int32)


def owned(D: int, slot: jax.Array, col, c: int, s: int, uplink: str):
    """``(n, D)`` ownership of a leaf's flat coordinates; ``slot`` is each
    row's cohort slot (-1 for rows out of the cohort)."""
    k = jnp.arange(D, dtype=jnp.int32)[None, :]
    a = slot[:, None]
    if uplink == "block_rs":
        blk = k // (-(-D // c))
        own = jnp.mod(blk - a - col, c) < s
    else:
        p = col[jnp.clip(a, 0)]
        own = jnp.mod(p - s * jnp.mod(k, c), c) < s
    return own & (a >= 0)


@partial(jax.jit, static_argnames=("c", "s", "uplink", "scale"),
         donate_argnums=(1,))
def comm_leaf(xs, hs, col, *, c, s, uplink, scale):
    """One leaf's UpCom and control-variate update over the cohort's rows
    (``xs``, ``hs``: one array per cohort slot, in slot order).  Returns
    ``xbar`` and the owners' new ``h`` rows."""
    shape = xs[0].shape
    D = int(np.prod(shape))
    xf = jnp.stack([a.reshape(D) for a in xs])
    hf = jnp.stack([a.reshape(D) for a in hs])
    own = owned(D, jnp.arange(c, dtype=jnp.int32), col, c, s, uplink)
    xbar = jnp.where(own, xf, 0.0).sum(0) / s
    h_new = hf + scale * jnp.where(own, xbar[None] - xf, 0.0)
    return xbar.reshape(shape), [h_new[a].reshape(shape) for a in range(c)]


@jax.jit
def sq_dist(row, row0):
    """Per-leaf squared L2 distance of one row's leaves from ``row0``'s."""
    return [jnp.sum(jnp.square(a - b)) for a, b in zip(row, row0)]


def leaf_norms(x, x0) -> List[float]:
    """Per-leaf L2 norm of ``x - x0`` over all rows: rows that are still
    ``x0`` itself add nothing and are skipped."""
    total = np.zeros(len(x0))
    for row in x:
        if row is not x0:
            total += np.asarray(jax.device_get(sq_dist(row, x0)), np.float64)
    return [float(v) for v in np.sqrt(total)]


def run_rounds(*, x0, n: int, loss_fn: Callable, sample: Callable, data,
               key0, lengths: Sequence[int], c: int, s: int, uplink: str,
               gamma: float, eta: float, norms_after: Sequence[int]
               ) -> Tuple[List[float], Dict[int, List[float]]]:
    """Replay ``len(lengths)`` rounds of ``n`` rows that all start at the
    params ``x0`` (one row), with ``h`` at 0.  Each row is a list of
    leaves of its own; a row no round has touched is ``x0`` itself, so
    memory follows the rows the rounds touch, not ``n``.  Returns each
    round's mean loss over its steps and cohort clients, and the per-leaf
    norms of ``x - x0`` after each round in ``norms_after`` (1-based)."""
    leaves0, treedef = jax.tree.flatten(x0)
    kd, kc = jax.random.split(key0)
    x = [leaves0] * n
    h = [None] * n
    grad = jax.jit(jax.value_and_grad(
        lambda leaves, b: loss_fn(jax.tree.unflatten(treedef, leaves), b)))
    take = jax.jit(lambda tree, i: jax.tree.map(lambda a: a[i], tree))
    step = jax.jit(lambda xi, g, hi: [a - gamma * (b - e)
                                      for a, b, e in zip(xi, g, hi)])
    zeros = jax.jit(lambda leaves: [jnp.zeros_like(a) for a in leaves])
    losses, norms, t = [], {}, 0
    for r, L in enumerate(lengths):
        kr = jax.random.fold_in(kc, r)
        cohort = cohort_of(kr, n, c)
        rows = [int(i) for i in np.asarray(cohort)]
        down = (np.asarray(cohort_of(jax.random.fold_in(kc, r + 1), n, c))
                if c < n else np.arange(n))
        for i in rows:
            if h[i] is None:
                h[i] = zeros(leaves0)
        total = 0.0
        for _ in range(L):
            batch = sample(data, jax.random.fold_in(kd, t), clients=cohort)
            for a, i in enumerate(rows):
                lv, g = grad(x[i], take(batch, a))
                x[i] = step(x[i], g, h[i])
                total += float(lv)
            t += 1
        losses.append(total / (L * c))
        col = template_column(kr, c, uplink)
        xbar = []
        h_rows = [list(h[i]) for i in rows]
        for j in range(len(leaves0)):
            xb, hj = comm_leaf(tuple(x[i][j] for i in rows),
                               tuple(hr[j] for hr in h_rows), col, c=c, s=s,
                               uplink=uplink, scale=float(eta / gamma))
            xbar.append(xb)
            for hr, v in zip(h_rows, hj):
                hr[j] = v
        for i, hr in zip(rows, h_rows):
            h[i] = hr
        for i in down:
            x[int(i)] = list(xbar)
        if r + 1 in norms_after:
            norms[r + 1] = leaf_norms(x, leaves0)
    return losses, norms
