"""Random weights from the seed, in the program's parameter layout.

The benchmark makes them itself, so the reference never reads weights
the program made.  Each leaf draws from its own stream (its index in the
tree), by the last key of its path: norm gains ``scale`` ~ 1 + N(0,
0.02^2), biases and the embedding ~ N(0, 0.02^2), every other matrix ~
N(0, 1/fan_in) with fan_in its second-to-last axis."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _leaf(key, name: str, s: jax.ShapeDtypeStruct) -> jax.Array:
    z = jax.random.normal(key, s.shape, jnp.float32)
    if name == "scale":
        a = 1.0 + 0.02 * z
    elif name == "embed" or len(s.shape) == 1 or name.startswith("b"):
        a = 0.02 * z
    else:
        a = z / jnp.sqrt(jnp.float32(s.shape[-2]))
    return a.astype(s.dtype)


def make_params(key: jax.Array, struct):
    """A params tree shaped like ``struct`` (ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(struct)
    leaves = [_leaf(jax.random.fold_in(key, i), str(path[-1].key), s)
              for i, (path, s) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)
