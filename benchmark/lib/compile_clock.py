"""Seconds jax spent compiling, cache hits and misses, each with the time
it happened, from jax's own monitoring events (copied from
``chip_smoke.CompileClock``; the event times are what is new: they say
whether anything compiled inside the measured window)."""

from __future__ import annotations

import threading
import time


class CompileClock:
    def __init__(self):
        from jax import monitoring

        self._lock = threading.Lock()
        #: (perf_counter at its end, seconds, function) of every program jax
        #: compiled OR loaded from the persistent cache (the event wraps both)
        self.compiles = []
        #: perf_counter of every persistent-cache hit / miss
        self.hits = []
        self.misses = []
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, fun_name="?", **_):
        if event.endswith("backend_compile_duration"):
            with self._lock:
                self.compiles.append(
                    (time.perf_counter(), float(secs), str(fun_name))
                )

    def _on_event(self, event, **_):
        now = time.perf_counter()
        with self._lock:
            if event.endswith("/cache_hits"):
                self.hits.append(now)
            elif event.endswith("/cache_misses"):
                self.misses.append(now)

    def between(self, t0: float, t1: float) -> dict:
        """What happened in [t0, t1] on the perf_counter clock.  A program
        counts where its compile (or its load from the cache) ENDED."""
        with self._lock:
            comp = [(s, n) for t, s, n in self.compiles if t0 <= t <= t1]
            return {
                "compiles": len(comp),
                "compile_seconds": sum(s for s, _ in comp),
                "compiled": sorted({n for _, n in comp})[:20],
                "cache_hits": sum(t0 <= t <= t1 for t in self.hits),
                "cache_misses": sum(t0 <= t <= t1 for t in self.misses),
            }
