"""Counts XLA backend compiles and persistent-cache loads, and sums their
seconds.

JAX reports a load from the persistent cache under the same duration event
as a compile, and counts the load as a cache hit besides; a compile is an
event that was not a hit.  A warm run's set-up reports loads only.
"""
from __future__ import annotations


class CompileClock:
    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.seconds, self.count, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_hit)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def _on_hit(self, event, **_):
        if event == self.HIT:
            self.hits += 1

    def take(self) -> tuple:
        """(seconds, compiles, cache loads) since the last take, then
        reset."""
        taken = (self.seconds, self.count - self.hits, self.hits)
        self.seconds, self.count, self.hits = 0.0, 0, 0
        return taken
