"""Backend compiles and persistent-cache hits and misses, from JAX's
monitoring events (a copy of the chip smoke's counter, with a count of
compiles so that a run can say how many fell inside its window)."""
from __future__ import annotations

import threading

import jax


class CompileStats:
    def __init__(self):
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration
                self.compiles += 1

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles, "seconds": self.seconds,
                    "hits": self.hits, "misses": self.misses}
