"""Count what JAX compiles and what its persistent cache serves, by
``jax.monitoring`` listeners of the benchmark's own (the program's
``obs`` counters are not read)."""

from __future__ import annotations

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HITS = "/jax/compilation_cache/cache_hits"
CACHE_MISSES = "/jax/compilation_cache/cache_misses"


class CompileLog:
    """``mark(phase)`` opens a phase; every backend compile (a
    persistent-cache load counts: the event wraps both) and every
    cache hit or miss lands in the open phase."""

    def __init__(self):
        self.phases: dict[str, dict] = {}
        self._open = None
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def mark(self, phase: str) -> None:
        self._open = self.phases.setdefault(phase, {
            "backend_compiles": 0, "backend_compile_s": 0.0,
            "cache_hits": 0, "cache_misses": 0})

    def _dur(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE and self._open is not None:
            self._open["backend_compiles"] += 1
            self._open["backend_compile_s"] += duration

    def _event(self, event: str, **_kw) -> None:
        if self._open is None:
            return
        if event == CACHE_HITS:
            self._open["cache_hits"] += 1
        elif event == CACHE_MISSES:
            self._open["cache_misses"] += 1
