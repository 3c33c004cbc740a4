"""Compile work seen through ``jax.monitoring``."""
from __future__ import annotations

import jax

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileLog:
    """Seconds spent tracing, lowering and compiling, how often that
    happened, and persistent-cache hits and misses. ``mark()`` starts a
    new count, so ``since_mark`` is what compiled inside a window."""

    def __init__(self):
        self.seconds = 0.0
        self.events = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self._mark = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name in _COMPILE_EVENTS:
            self.seconds += secs
            self.events += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self):
        self._mark = self.events

    @property
    def since_mark(self) -> int:
        return self.events - self._mark
