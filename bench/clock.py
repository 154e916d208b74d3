"""What the benchmark reads from JAX itself: compile time and cache hits
(``jax.monitoring`` events), and the device allocator's peak."""

from __future__ import annotations

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or loading from
    the persistent cache), how many programs it lowered and handed the
    backend, and the persistent-cache hits, since ``reset``.  Listeners
    cannot be removed, so make one per process."""

    def __init__(self):
        import jax

        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.secs += secs
        if event == BACKEND_COMPILE:
            self.compiles += 1
        if event == COMPILE_EVENTS[1]:
            self.lowerings += 1

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1

    def reset(self):
        self.secs, self.compiles, self.lowerings, self.hits = 0.0, 0, 0, 0

    def snapshot(self) -> dict:
        return {"compile_s": self.secs, "programs": self.compiles,
                "lowerings": self.lowerings, "cache_hits": self.hits}


def peak_bytes(devices) -> int:
    """Largest peak over ``devices`` of the buffers in use plus what the
    runtime reserved for programs' temporaries (``peak_bytes_in_use`` +
    ``peak_bytes_reserved``: on a TPU the first leaves the temporaries
    out); 0 where the backend keeps no allocator statistics, as the CPU
    does."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0)) for s in stats)
