"""Compile seconds and persistent-cache hits from JAX's monitoring
events (a copy of ``chip_smoke.CompileMeter``, kept with the benchmark).
"""
from __future__ import annotations

import jax

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    """Backend compile seconds and count, and persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == _BACKEND_COMPILE:
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def programs_fetched(self) -> int:
        """Programs compiled or loaded from the persistent cache."""
        return self.compiles + self.cache_hits
