"""Readings of the program's own spans and counters (``repro.core.obs``)
in a traced window.

The program records its host work as spans on the wall clock, the clock
of the benchmark's own spans, so :func:`benchlib.trace.align` moves both
onto the trace's clock with one offset, and :func:`benchlib.trace.reduce`
then attributes each idle gap to the innermost of either. On top of
that, :func:`module_ns_by_span` credits each device program's time to
every span open when it started, which splits the counting programs by
the tier that dispatched them (``count.space``, ``count.ground``).

Each reader takes the traced run (``trace``: the reduced window with
the program spans among its spans; ``tally``; ``counters``: the
program's counter deltas over the window; ``module_ns_by_span``;
``devices``: the trace's device events) and returns ``None`` where its
input is absent, as a program without these spans and counters gives.

These readers are the per-layer metrics a traced run of
``bench/run.py`` would report once its traced window turns the
program's recorder on; until then ``bench/spans.py`` reads them.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Tuple

Event = Tuple[str, int, int]

COUNT_PROGRAMS = ("jit_count_tiles", "jit__count_tiles_chunks")
FRAME_PROGRAMS = ("jit__frame_program_body", "jit__frame_program_multi")
TIERS = ("count.space", "count.ground")


def module_ns_by_span(devices: Dict[str, Dict[str, List[Event]]],
                      spans: List[Event], t0: int, t1: int
                      ) -> Dict[Tuple[str, str], float]:
    """Device time of each jitted program inside ``[t0, t1)``, credited
    to every span (by name, once) that contains the program's start,
    averaged over the chips. -> {(span, program): ns}."""
    from benchlib.trace import module_name
    order = sorted(spans, key=lambda sp: sp[1])
    starts = [sp[1] for sp in order]
    nd = max(len(devices), 1)
    out: Dict[Tuple[str, str], float] = defaultdict(float)
    for lines in devices.values():
        for name, s, e in lines["modules"]:
            inside = min(e, t1) - max(s, t0)
            if inside <= 0:
                continue
            hi = bisect.bisect_right(starts, s)
            names = {sp[0] for sp in order[:hi] if sp[2] > s}
            for sp in names:
                out[(sp, module_name(name))] += inside / nd
    return dict(out)


def host_ms_by_span(spans: List[Event], t0: int, t1: int
                    ) -> Dict[str, float]:
    """Host milliseconds of every span name inside ``[t0, t1)``."""
    out: Dict[str, float] = defaultdict(float)
    for name, s, e in spans:
        if min(e, t1) > max(s, t0):
            out[name] += (min(e, t1) - max(s, t0)) / 1e6
    return dict(out)


def _per_round(run, ms):
    rounds = run["tally"]["rounds"]
    return ms / rounds if rounds and ms > 0 else None


def _device(run, spans, keep):
    by = run.get("module_ns_by_span")
    if not by:
        return None
    return _per_round(run, sum(v for (sp, mod), v in by.items()
                               if sp in spans and keep(mod)) / 1e6)


def capture_host_fill_ms_per_round(run):
    """Host time filling the capture's frame buffers, per round."""
    t = run["trace"]
    fill = [sp for sp in t.spans if sp[0] == "capture.fill"]
    return _per_round(run, host_ms_by_span(fill, t.t0, t.t1).get(
        "capture.fill", 0.0))


def h2d_in_flight_ns(devices: Dict[str, Dict[str, List[Event]]],
                     spans: List[Event], t0: int, t1: int) -> float:
    """Nanoseconds from the start of each frame buffer's copy to the
    device (``capture.to_device``) to the start of the first frame
    program after it and before the next copy, summed over ``[t0, t1)``,
    averaged over the chips. The host call returns before the copy is
    done and the program waits for it, so this is the copy's time in
    flight (with any device work queued ahead of the program)."""
    from benchlib.trace import module_name
    starts = sorted(s for n, s, _ in spans
                    if n == "capture.to_device" and t0 <= s < t1)
    total = 0.0
    for lines in devices.values():
        progs = sorted(s for n, s, _ in lines["modules"]
                       if module_name(n) in FRAME_PROGRAMS)
        for a, b in zip(starts, starts[1:] + [t1]):
            i = bisect.bisect_left(progs, a)
            if i < len(progs) and progs[i] < b:
                total += progs[i] - a
    return total / max(len(devices), 1)


def capture_h2d_ms_per_round(run):
    """Time the frame buffers' copies to the device were in flight, per
    round: from the copy's start to the frame program's."""
    devices = run.get("devices")
    if not devices:
        return None
    t = run["trace"]
    return _per_round(run, h2d_in_flight_ns(devices, t.spans, t.t0, t.t1)
                      / 1e6)


def count_ground_device_ms_per_round(run):
    """Device time of the counting programs the ground tier dispatched."""
    return _device(run, ("count.ground",), lambda m: m in COUNT_PROGRAMS)


def count_space_device_ms_per_round(run):
    """Device time of the counting programs the space tier dispatched."""
    return _device(run, ("count.space",), lambda m: m in COUNT_PROGRAMS)


def count_copy_device_ms_per_round(run):
    """Device time of every other program either tier dispatched: the
    tile gather, the pad concatenation, the result stack."""
    return _device(run, TIERS, lambda m: m not in COUNT_PROGRAMS)


def count_pad_share(run):
    """Share of the rows the count batches computed that were padding."""
    c = run.get("counters") or {}
    real, comp = c.get("count.rows_real"), c.get("count.rows_computed")
    if not comp or real is None:
        return None
    return 100.0 * (comp - real) / comp


# name -> (unit, reader), as the per-layer metrics would be entered
METRICS = {
    "capture.host_fill_ms_per_round": ("ms", capture_host_fill_ms_per_round),
    "capture.h2d_ms_per_round": ("ms", capture_h2d_ms_per_round),
    "count.ground_device_ms_per_round": ("ms",
                                         count_ground_device_ms_per_round),
    "count.copy_device_ms_per_round": ("ms", count_copy_device_ms_per_round),
    "count.pad_share": ("%", count_pad_share),
}
