"""XLA compile seconds and persistent-cache hits, from JAX's own
monitoring events (a copy of the bring-up smoke's ``CompileClock``).

JAX records ``backend_compile_duration`` for every program it builds or
loads from the persistent cache, so ``programs`` counts both: a window in
which it does not move compiled and loaded nothing."""
from __future__ import annotations

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        import jax
        self.compile_s = 0.0      # compile or cache-load seconds
        self.programs = 0         # programs compiled or loaded
        self.cache_hits = 0       # of them, loaded from the persistent cache
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.compile_s += duration
            self.programs += 1

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s, "programs": self.programs,
                "cache_hits": self.cache_hits}
