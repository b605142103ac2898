"""Spans and counters of the program's host work.

One recorder for the whole program:

* :func:`span` times a block of host work. While the recorder is off
  (the default) it returns one shared null context and records nothing,
  so an instrumented call site costs a flag test. While it is on, each
  span records ``(name, start_ns, end_ns, parent, thread)`` on the wall
  clock (:func:`time.time_ns`); the parent is the innermost span open on
  the same thread, so a worker thread's spans never nest under the
  foreground's.
* :func:`count` adds to a named integer counter. Counters are always on
  and lock-guarded (recount workers write them too); they take host
  integers only, never device values.
* While the recorder is on, every backend compile is counted under the
  innermost span open on the compiling thread, as ``compile@<span>``,
  which names the step that recompiled.

Spans time host work only: none of them waits for the device. Device
time is read from a profiler trace; a trace reader can place the spans
on the trace's clock, since they share its wall clock.

    obs.enable()
    ... run rounds ...
    obs.disable()
    obs.events()     # [(name, start_ns, end_ns), ...] of this thread
    obs.counters()   # {name: int}, a snapshot
"""
from __future__ import annotations

import threading
import time
from contextlib import nullcontext
from typing import Dict, List, NamedTuple, Optional, Tuple

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
NO_SPAN = "(no span)"


class Record(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]   # innermost span open on the same thread
    thread: int             # threading.get_ident() of the recording thread


_lock = threading.Lock()
_counters: Dict[str, int] = {}
_records: List[Record] = []
_local = threading.local()
_NULL = nullcontext()
_on = False


def _stack() -> List[str]:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


class _Span:
    __slots__ = ("name", "parent", "t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = _stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.t0 = time.time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.time_ns()
        _stack().pop()
        if _on:
            rec = Record(self.name, self.t0, t1, self.parent,
                         threading.get_ident())
            with _lock:
                _records.append(rec)
        return False


def span(name: str):
    """Context manager timing one block of host work (see module doc)."""
    if not _on:
        return _NULL
    return _Span(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` (a host integer) to the counter ``name``."""
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def counters() -> Dict[str, int]:
    """Snapshot of every counter (a copy; diff two to get a window's)."""
    with _lock:
        return dict(_counters)


def reset(*names: str) -> None:
    """Zero the named counters."""
    with _lock:
        for name in names:
            _counters.pop(name, None)


def _on_duration(event: str, secs: float, **kw) -> None:
    if _on and event == _COMPILE_EVENT:
        stack = _stack()
        count("compile@" + (stack[-1] if stack else NO_SPAN))


def enable() -> None:
    """Start recording spans (dropping earlier records) and compiles."""
    global _on
    import jax.monitoring as mon
    with _lock:
        _records.clear()
        if not _on:
            mon.register_event_duration_secs_listener(_on_duration)
        _on = True


def disable() -> None:
    """Stop recording; the records stay readable until the next enable."""
    global _on
    import jax.monitoring as mon
    with _lock:
        if _on:
            mon.unregister_event_duration_listener(_on_duration)
        _on = False


def enabled() -> bool:
    return _on


def records() -> List[Record]:
    """Every span recorded since :func:`enable`, on every thread, in the
    order the spans ended."""
    with _lock:
        return list(_records)


def events() -> List[Tuple[str, int, int]]:
    """The calling thread's spans as ``(name, start_ns, end_ns)``."""
    me = threading.get_ident()
    return [(r.name, r.start_ns, r.end_ns) for r in records()
            if r.thread == me]
