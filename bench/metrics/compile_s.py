"""Seconds JAX traced, lowered and compiled (or loaded from the cache) in
set-up: the round engine's bucket programs and the set-up's own."""


def read(run):
    return run.setup["compile_s"]
