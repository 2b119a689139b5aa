"""Compile meter: jax's own monitoring events, not the wall clock.

``/jax/core/compile/backend_compile_duration`` wraps the backend's
compile-or-load: on a persistent-cache miss it times the XLA compile, on
a hit the retrieval. Each event is counted too, so a compile inside the
measured window shows as a count above zero. (A copy of the program's
``launch.sweep.compile_meter``, kept here so the yardstick cannot move.)
"""
from __future__ import annotations

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    def __init__(self):
        import jax.monitoring as jmon

        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jmon.register_event_listener(self._on_event)
        jmon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def snapshot(self) -> dict:
        return {"seconds": self.seconds, "count": self.count,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}
