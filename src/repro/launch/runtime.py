"""Process-level set-up shared by the entry points (``train``, ``serve``,
``chip_smoke.py``).  Nothing here runs at import time."""

from __future__ import annotations

import os

# <repo>/.jax_cache: a fixed path, so one run's compiles are found again by
# the next (the cache key includes the directory)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR


def device_info() -> dict:
    """The first device as JAX reports it, and how many there are."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
