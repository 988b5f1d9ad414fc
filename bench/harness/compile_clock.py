"""Seconds and count of JAX backend compiles, from ``jax.monitoring``.

``backend_compile_duration`` wraps both a real compile and a load from the
persistent compilation cache; ``cache_misses`` counts the real compiles
among them.
"""
from __future__ import annotations

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
MISS_EVENT = "/jax/compilation_cache/cache_misses"


class CompileClock:
    def __init__(self):
        self.seconds = 0.0
        self.count = 0       # compiles and cache loads
        self.misses = 0      # compiles the persistent cache could not serve
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def _on_event(self, event, **_):
        if event == MISS_EVENT:
            self.misses += 1
