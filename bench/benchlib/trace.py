"""Reduce a profiler trace of the measured window to numbers.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``:
device planes (``/device:TPU:<i>``) carry the operations that ran on each
chip (line ``XLA Ops``) and the jitted programs they belong to (line
``XLA Modules``), in nanoseconds from the trace's start. The
benchmark's own host spans (``bench.*``) are kept by :data:`SPANS` on
the host's wall clock, and not by the profiler's host tracer: that
tracer records every chunk of the host's layout transpose of each pass's
frames, which made a traced round several times slower than an untraced
one. :func:`clock_mark` runs a tiny program before and after the window,
and :func:`align` moves the spans onto the trace's clock by where those
programs ended.
:func:`load` turns the trace into plain events, and everything else
here works on plain ``(name, start_ns, end_ns)`` tuples, so it can be
checked on a small recorded trace without a chip.
"""
from __future__ import annotations

import contextlib
import functools
import glob
import os
import re
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

Event = Tuple[str, int, int]  # name, start ns, end ns

SPAN_PREFIX = "bench."
NO_SPAN = "(no benchmark span)"


def merge(intervals) -> List[Tuple[int, int]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[int]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: int, t1: int):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def gaps(busy, t0: int, t1: int) -> List[Tuple[int, int]]:
    """The parts of [t0, t1) that no busy interval covers."""
    out, cur = [], t0
    for s, e in clip(busy, t0, t1):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def attribute(idle, spans: List[Event]) -> Dict[str, int]:
    """Idle nanoseconds by the innermost host span open at the time (the
    one opened last among those that cover it)."""
    out: Dict[str, int] = defaultdict(int)
    for g0, g1 in idle:
        cover = [sp for sp in spans if sp[1] < g1 and sp[2] > g0]
        cuts = sorted({g0, g1} | {t for sp in cover for t in sp[1:]
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            inner = [sp for sp in cover if sp[1] <= a and sp[2] >= b]
            name = max(inner, key=lambda sp: (sp[1], -sp[2]))[0] \
                if inner else NO_SPAN
            out[name] += b - a
    return dict(out)


class Spans:
    """Host spans on the wall clock, recorded while :attr:`on`; a no-op
    otherwise, so the measured window pays nothing for them."""

    def __init__(self):
        self.on = False
        self.events: List[Event] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        if not self.on:
            yield
            return
        t0 = time.time_ns()
        try:
            yield
        finally:
            self.events.append((name, t0, time.time_ns()))


SPANS = Spans()
MARK = "jit_bench_clock_mark"


def clock_mark() -> int:
    """Run the marker program on the device and wait for it; -> the host
    wall clock (ns) right after it ended."""
    import jax
    import jax.numpy as jnp
    jax.block_until_ready(_mark_program()(jnp.zeros((), jnp.float32)))
    return time.time_ns()


@functools.lru_cache(maxsize=None)
def _mark_program():
    import jax

    def bench_clock_mark(x):
        return x + 1.0
    return jax.jit(bench_clock_mark)


def align(spans: List[Event], marks: List[int],
          devices: Dict[str, Dict[str, List[Event]]]):
    """Host spans on the wall clock -> the same spans on the trace's
    clock. ``marks``: the wall-clock times :func:`clock_mark` returned,
    in order; each is matched with the end of the marker program's n-th
    run on the first chip. -> (spans, the marks' offsets disagreeing by
    this many ns)."""
    first = devices[sorted(devices)[0]]["modules"]
    ends = sorted(e for name, _, e in first if module_name(name) == MARK)
    if len(ends) != len(marks):
        raise ValueError(f"{len(marks)} clock marks, {len(ends)} marker "
                         f"programs in the trace")
    offs = [m - e for m, e in zip(marks, ends)]
    off = min(offs)  # the host returns after the device ends
    return ([(n, s0 - off, s1 - off) for n, s0, s1 in spans],
            max(offs) - off)


_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """``jit_count_tiles(123)`` -> ``jit_count_tiles``."""
    return _SUFFIX.sub("", name)


_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTR = re.compile(r"\s*(%?[\w.\-]+) = (\([^()]*\)|\S+) ([\w\-]+)")


def op_label(name: str) -> str:
    """An XLA op event is named by its HLO instruction
    (``%fusion.87 = f32[64,416,416,32]{0,3,2,1:T(8,128)} fusion(...),
    kind=...``): keep its name, shape and opcode, without layouts and
    operands. A kernel's custom call is named after the program that
    holds it (``%_frame_program_body.3 = ... custom-call(...)``), so the
    opcode is what finds it."""
    s, prev = name, None
    while s != prev:
        s, prev = _LAYOUT.sub("", s), s
    m = _INSTR.match(s)
    if m:
        return " ".join((m.group(1), "=", m.group(2), m.group(3)))[:120]
    return s.split("(", 1)[0].strip()[:120]


def _owner(modules, t: int):
    """The module event (sorted by start) that holds time ``t``."""
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][1] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and modules[lo - 1][2] > t:
        return module_name(modules[lo - 1][0])
    return "(no module)"


@dataclass
class Summary:
    """The reduced trace of one window, averaged over the chips used."""
    t0: int
    t1: int
    n_devices: int
    busy_ns: float                      # mean over chips
    module_ns: Dict[str, float]         # per jitted program, mean over chips
    op_ns: Dict[Tuple[str, str], float]     # per (program, op), mean
    op_count: Dict[Tuple[str, str], float]  # calls per (program, op), mean
    idle_by_span: Dict[str, float]      # mean idle ns by host span
    spans: List[Event] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def module_s(self, *names: str) -> float:
        """Device seconds of the jitted programs whose names (without the
        ``jit_`` prefix) are in ``names``."""
        want = {n if n.startswith("jit_") else "jit_" + n for n in names}
        return sum(v for k, v in self.module_ns.items() if k in want) / 1e9

    def ops_matching(self, pattern: str, *programs: str):
        """(seconds, calls) of device operations whose name matches
        ``pattern``, inside the jitted ``programs`` (any, if none)."""
        rx = re.compile(pattern)
        want = {p if p.startswith("jit_") else "jit_" + p for p in programs}
        hit = [k for k in self.op_ns
               if rx.search(k[1]) and (not want or k[0] in want)]
        return (sum(self.op_ns[k] for k in hit) / 1e9,
                sum(self.op_count[k] for k in hit))

    def top_ops(self, n: int = 10):
        return [[f"{m}: {op}", v / 1e9] for (m, op), v in sorted(
            self.op_ns.items(), key=lambda kv: -kv[1])[:n]]

    def top_idle(self, n: int = 10):
        return [[k, v / 1e9] for k, v in sorted(
            self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]]


def reduce(devices: Dict[str, Dict[str, List[Event]]], spans: List[Event],
           window: str = SPAN_PREFIX + "window") -> Summary:
    """``devices``: device -> {"ops": [...], "modules": [...]} events;
    ``spans``: host spans. The window is the span named ``window``."""
    win = [sp for sp in spans if sp[0] == window]
    if not win:
        raise ValueError(f"no {window!r} span in the trace")
    t0, t1 = win[0][1], win[0][2]
    inner = [sp for sp in spans if sp[0] != window]
    nd = max(len(devices), 1)
    busy = 0.0
    mods: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    calls: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    for lines in devices.values():
        intervals = merge(clip([(s, e) for _, s, e in lines["ops"]], t0, t1))
        busy += sum(e - s for s, e in intervals) / nd
        modules = sorted(lines["modules"], key=lambda ev: ev[1])
        for name, s, e in modules:
            if e > t0 and s < t1:
                mods[module_name(name)] += (min(e, t1) - max(s, t0)) / nd
        for name, s, e in lines["ops"]:
            if e > t0 and s < t1:
                key = (_owner(modules, s), op_label(name))
                ops[key] += (min(e, t1) - max(s, t0)) / nd
                calls[key] += 1 / nd
        for k, v in attribute(gaps(intervals, t0, t1), inner).items():
            idle[k] += v / nd
    return Summary(t0, t1, len(devices), busy, dict(mods), dict(ops),
                   dict(calls), dict(idle), inner)


def load(trace_dir: str, n_devices: int):
    """Read the newest ``.xplane.pb`` under ``trace_dir`` -> the devices'
    events for :func:`reduce`, keeping the first ``n_devices`` chips."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices: Dict[str, Dict[str, List[Event]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "TPU" in plane.name:
            rest = plane.name[len("/device:TPU:"):]
            if not rest.isdigit() or int(rest) >= n_devices:
                continue
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    lines[key] += [(e.name, int(e.start_ns),
                                    int(e.start_ns + e.duration_ns))
                                   for e in line.events]
            devices[plane.name] = lines
    return devices
