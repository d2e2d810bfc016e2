"""Counts this process's XLA compiles and persistent-cache hits through
jax.monitoring (copied from chip_smoke.CompileMeter; listeners cannot be
unregistered, so make one per process)."""

import threading


class CompileMeter:
    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self._lock = threading.Lock()  # the prefetch thread may compile too
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == self.BACKEND_COMPILE:
            with self._lock:
                self.compiles += 1

    def _event(self, event, **_):
        if event == self.CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return self.compiles, self.cache_hits
