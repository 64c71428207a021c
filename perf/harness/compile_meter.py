"""JAX's own compile and persistent-cache events, summed by phase."""
from __future__ import annotations

from typing import Dict

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileMeter:
    """Counts trace + lowering + backend-compile seconds (a cache hit counts
    its retrieval), backend compiles, and persistent-cache hits and misses.
    ``snapshot()`` returns the totals so far; the harness takes one at the
    window's start and one at its end — the difference must be empty."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, name: str, secs: float, **_) -> None:
        if name in COMPILE_EVENTS:
            self.seconds += secs
        if name == BACKEND_COMPILE:
            self.compiles += 1

    def _on_event(self, name: str, **_) -> None:
        if name == CACHE_HIT:
            self.hits += 1
        elif name == CACHE_MISS:
            self.misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}
