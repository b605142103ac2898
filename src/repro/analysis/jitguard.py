"""Runtime jit-recompilation sanitizer.

The fleet engine's whole shape discipline — pow2 frame buckets,
size-tiered counting batches, padded dedup cores — exists so that
steady-state rounds re-dispatch *already-compiled* XLA programs.  A
regression that lets a data-dependent shape reach a jit boundary shows
up as a recompile per round: silent, correct, and catastrophically slow.
:class:`JitGuard` counts XLA compilations inside a ``with`` block so
benches and tests can assert the steady state compiles nothing:

    with JitGuard() as g:
        fleet.ingest(frames, harvest)          # round >= 2, fixed sizes
    g.assert_steady_state("fleet round 3")     # raises if g.compilations

The signal is ``jax.monitoring`` duration events — jax emits
``/jax/core/compile/backend_compile_duration`` once per backend
compilation (verified: cache hits emit nothing).
"""
from __future__ import annotations

import threading

import jax.monitoring as mon

_COMPILE_EVENT_PREFIX = "/jax/core/compile/backend_compile"


class JitGuard:
    """Context manager counting XLA compilations in its dynamic extent.

    Thread-safe: compilations from worker threads (the GroundSegment
    recount pipeline) are counted too — the monitoring listener is
    process-global and guarded by a lock.
    """

    def __init__(self, label: str = ""):
        self.label = label
        self.compilations = 0
        self._lock = threading.Lock()

    def _on_duration(self, name: str, secs: float, **kw) -> None:
        if name.startswith(_COMPILE_EVENT_PREFIX):
            with self._lock:
                self.compilations += 1

    def __enter__(self) -> "JitGuard":
        self.compilations = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc) -> bool:
        mon.unregister_event_duration_listener(self._on_duration)
        return False

    def assert_steady_state(self, what: str = "") -> None:
        """Raise if the guarded block compiled any new XLA program."""
        if self.compilations:
            label = what or self.label or "guarded block"
            raise AssertionError(
                f"jitguard: {label} compiled {self.compilations} new XLA "
                f"program(s); steady-state rounds must re-dispatch "
                f"already-compiled programs only (shape churn reached a "
                f"jit boundary — check pow2 bucketing / tier floors)")
