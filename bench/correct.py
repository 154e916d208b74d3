"""The comparison that decides ``correct``: the program's first rounds
against the plain reference's, by three numbers.

* ``loss_gap``: the largest relative gap of a round's mean loss over the
  first three rounds.
* ``grad_gap``: the first round's update ``(x0 - x1) / gamma`` per leaf
  (h starts at 0, so this is the gradient as the optimizer takes it, after
  the round's exchange): the largest gap between the program's and the
  reference's norms of a leaf, over the reference's norm of that leaf or
  of the median leaf, whichever is larger.
* ``change_gap``: the same for ``x3 - x0``, the change after three rounds,
  over the leaves that move: a leaf whose reference first update is under
  a thousandth of the median leaf's (a key bias, which softmax makes
  gradient-free) moves by rounding alone and is left out.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

LIMIT_KEYS = ("loss_gap", "grad_gap", "change_gap")
STILL = 1e-3  # a leaf under this share of the median leaf's update is still


@jax.jit
def leaf_norms(x, x0):
    """Per-leaf L2 norm of ``x - x0`` (``x0`` one row, broadcast over the
    client axis), in float32."""
    return [jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)[None])))
        for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(x0))]


def readings(losses: List[float], norms1, norms3, gamma: float) -> Dict:
    return {"loss": [float(v) for v in losses],
            "grad": np.asarray([float(v) for v in norms1]) / gamma,
            "change": np.asarray([float(v) for v in norms3])}


def _norm_gap(prog, ref, keep):
    floor = max(float(np.median(ref)), 1e-30)
    gaps = np.abs(prog - ref) / np.maximum(ref, floor)
    return float(gaps[keep].max())


def gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    med = float(np.median(ref["grad"]))
    moving = ref["grad"] >= STILL * med
    lp, lr = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": _norm_gap(prog["grad"], ref["grad"],
                              np.ones_like(moving)),
        "change_gap": _norm_gap(prog["change"], ref["change"], moving),
    }


def judge(g: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number finite and at or under its limit."""
    return all(np.isfinite(g[k]) and g[k] <= limits[k] for k in LIMIT_KEYS)


def report(g: Dict[str, float], limits: Dict[str, float]) -> Dict:
    return {k: {"value": g[k], "limit": limits[k]} for k in LIMIT_KEYS}
