"""Compile time, compile count and persistent-cache hits, from
``jax.monitoring`` events (the listener of ``chip_smoke.py``'s ``Meter``
and of ``repro.analysis.jitguard.JitGuard``: JAX emits one
``backend_compile_duration`` event per backend compile and none on a
cache hit)."""
from __future__ import annotations

import threading

_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


class Meter:
    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()

    def _on_duration(self, name, secs, **_):
        if name == _COMPILE:
            with self._lock:
                self.compiles += 1
                self.compile_s += secs

    def _on_event(self, name, **_):
        with self._lock:
            if name == _HIT:
                self.hits += 1
            elif name == _MISS:
                self.misses += 1

    def __enter__(self):
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)
        return False
