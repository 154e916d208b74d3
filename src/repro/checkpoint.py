"""Sharded checkpointing: npz payloads + msgpack metadata.

Saves arbitrary pytrees (TAMUNA TrainState included) with the tree structure
and per-leaf dtype/shape recorded so restore works without reconstructing
the pytree first.  Device arrays are fetched shard-by-shard
(``jax.device_get``); restore re-places onto the provided shardings.

Saves are **atomic**: the payload is written into a staging directory next
to the target and ``os.replace``'d into place, so a crash mid-save (the
fault modes DESIGN.md §12 injects are exactly the kind that interrupt a
run) never leaves a half-written checkpoint where ``latest_step`` would
find it — a directory either holds a complete ``arrays.npz`` + ``meta.json``
pair or does not exist.
"""

from __future__ import annotations

import json
import os
import shutil
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

Params = Any


def _flatten_with_names(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names, leaves = [], []
    for path, leaf in flat:
        names.append(
            "/".join(str(getattr(e, "key", getattr(e, "idx", e)))
                     for e in path)
        )
        leaves.append(leaf)
    return names, leaves, treedef


def save(path: str, tree: Params, step: Optional[int] = None) -> None:
    path = os.path.normpath(path)
    parent = os.path.dirname(path) or "."
    os.makedirs(parent, exist_ok=True)
    # stage under a dot-prefixed sibling: same filesystem (so the final
    # os.replace is atomic) and invisible to latest_step's step_* scan
    stage = os.path.join(parent, f".tmp_{os.path.basename(path)}")
    if os.path.isdir(stage):
        shutil.rmtree(stage)
    os.makedirs(stage)
    try:
        names, leaves, treedef = _flatten_with_names(tree)
        arrays = {}
        for i, x in enumerate(leaves):
            a = np.asarray(jax.device_get(x))
            if a.dtype == jnp.bfloat16:  # npz has no bf16 cast: store raw bits
                a = a.view(np.uint16)
            arrays[f"leaf_{i}"] = a
        np.savez(os.path.join(stage, "arrays.npz"), **arrays)
        meta = {
            "names": names,
            "treedef": str(treedef),
            "step": step,
            "dtypes": [str(x.dtype) for x in leaves],
            "shapes": [list(x.shape) for x in leaves],
        }
        with open(os.path.join(stage, "meta.json"), "w") as f:
            json.dump(meta, f)
        if os.path.isdir(path):
            # os.replace cannot clobber a non-empty dir: drop the old
            # checkpoint only now that the replacement is fully staged
            shutil.rmtree(path)
        os.replace(stage, path)
    except BaseException:
        shutil.rmtree(stage, ignore_errors=True)
        raise


def _load_meta(path: str) -> Optional[dict]:
    try:
        with open(os.path.join(path, "meta.json")) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def restore(path: str, like: Params, shardings: Optional[Params] = None
            ) -> Params:
    """Restore into the structure of ``like`` (leaf order must match save).

    A leaf-count mismatch names the offending leaf *paths* (saved names
    vs the names of ``like``), not just the counts — the error you get
    when restoring into a state whose structure drifted across versions.
    Leaves that are numpy arrays or scalars in ``like`` come back as
    numpy arrays of their dtype; every other leaf as a jax array.
    """
    with np.load(os.path.join(path, "arrays.npz")) as z:
        arrays = [z[f"leaf_{i}"] for i in range(len(z.files))]
    names, leaves, treedef = _flatten_with_names(like)
    if len(arrays) != len(leaves):
        meta = _load_meta(path)
        saved = list(meta["names"]) if meta and "names" in meta else None
        msg = (f"checkpoint has {len(arrays)} leaves, expected "
               f"{len(leaves)}")
        if saved is not None:
            missing = sorted(set(saved) - set(names))
            extra = sorted(set(names) - set(saved))
            if missing:
                msg += f"; in checkpoint but not in target: {missing}"
            if extra:
                msg += f"; in target but not in checkpoint: {extra}"
        raise ValueError(msg)
    out = []
    for i, (arr, ref) in enumerate(zip(arrays, leaves)):
        if tuple(arr.shape) != tuple(ref.shape):
            raise ValueError(
                f"shape mismatch at leaf {names[i]!r}: "
                f"{tuple(arr.shape)} vs {tuple(ref.shape)}"
            )
        if ref.dtype == jnp.bfloat16 and arr.dtype == np.uint16:
            import ml_dtypes

            arr = arr.view(ml_dtypes.bfloat16)  # bit-exact restore
        if isinstance(ref, (np.ndarray, np.generic)):
            # host leaves stay host arrays: float64 survives, where a
            # jnp array would narrow it to float32 outside x64 mode
            out.append(np.asarray(arr, dtype=ref.dtype))
        else:
            out.append(jnp.asarray(arr, dtype=ref.dtype))
    tree = jax.tree.unflatten(jax.tree.structure(like), out)
    if shardings is not None:
        tree = jax.device_put(tree, shardings)
    return tree


def latest_step(root: str) -> Optional[int]:
    if not os.path.isdir(root):
        return None
    steps = [
        int(d.split("_")[-1]) for d in os.listdir(root)
        if d.startswith("step_")
    ]
    return max(steps) if steps else None
