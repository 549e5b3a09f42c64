"""Tracing, lowering and compilation seconds, from ``jax.monitoring``.

JAX reports three durations for each program it builds: tracing to a
jaxpr, lowering to an MLIR module, and the backend compile (a
persistent-cache hit reports the time to load the executable there).
Listeners cannot be removed, so one log lives for the process.
"""
from __future__ import annotations

from typing import Dict, Tuple

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    def __init__(self, jax):
        self.seconds = {e: 0.0 for e in EVENTS}
        self.count = {e: 0 for e in EVENTS}
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name in self.seconds:
            self.seconds[name] += secs
            self.count[name] += 1

    def _event(self, name, **_):
        if name == CACHE_HIT:
            self.cache_hits += 1

    def mark(self) -> Tuple[float, int, int]:
        return (sum(self.seconds.values()), sum(self.count.values()),
                self.cache_hits)

    def since(self, mark) -> Dict[str, float]:
        s, c, h = mark
        return {"seconds": sum(self.seconds.values()) - s,
                "events": sum(self.count.values()) - c,
                "cache_hits": self.cache_hits - h}
