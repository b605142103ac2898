"""Orbital geometry engine benchmarks: catalog-scale batched
propagation, the visibility grid, pass extraction, and eclipse masking.

**Propagation rows** — the headline: >= 4096 satellites x >= 1440 time
steps (``ORBITS_BENCH_SATS`` / ``ORBITS_BENCH_STEPS``) batch-propagated
through ONE jitted program (``propagate_jit``), timed post-warmup so
the number is steady-state execution, not compile time. A second row
propagates a full-catalog-sized scattered shell (14,368 objects — the
CelesTrak catalog size OrbVeil's validation batch-propagates in tens of
ms) over a short screening grid. The gate is sats x steps throughput
(``THROUGHPUT_GATE``), enforced only on full-size runs on
>= ``PERF_GATES_MIN_CORES``-core boxes (same policy as fleet_bench:
smoke configs and starved CI runners record honest numbers, null
gates).

**Visibility / eclipse rows** — the elevation grid
(stations x sats x times, one jitted program), the host-side
segment-scan pass extraction over that grid, and the cylindrical
Earth-shadow mask. The pass-extraction row also reports the pass-mix
skew (median vs p90 duration, max-elevation quartiles) — the
heavy-tailed many-grazes/few-overhead-passes distribution the orbital
scenario path feeds the contact tier.

Writes ``BENCH_orbits.json`` (redirect with ``ORBITS_BENCH_JSON`` —
smoke configs must not clobber the committed full-size report). Gate
failures raise AFTER the report lands, so ``run.py orbits``
exits nonzero while the JSON still records what happened.
"""
from __future__ import annotations

import json
import os
import time

import jax.numpy as jnp
import numpy as np

from repro.orbits import (elevation_deg, extract_passes, shell, sun_direction,
                          station_ecef, walker_delta)
from repro.orbits.propagation import propagate_jit
from repro.orbits.visibility import _eclipse_jit
from repro.orbits.schedule import default_sites

JSON_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_orbits.json")
# sats x steps per wall-second through the jitted propagator. Modest on
# purpose: a single contended CI core does ~0.5M; any >= 2-core box
# clears 1M with headroom. The honest number is always recorded.
THROUGHPUT_GATE = 1.0e6
PERF_GATES_MIN_CORES = 2
# the acceptance floor for the headline row
FULL_SATS, FULL_STEPS = 4096, 1440
CATALOG_SIZE = 14_368  # CelesTrak catalog size (OrbVeil validation)


def _perf_gates_enforced() -> bool:
    return (os.cpu_count() or 1) >= PERF_GATES_MIN_CORES


def _time_s(fn, *args, iters=3):
    out = fn(*args)
    out.block_until_ready()  # warm: compile + first dispatch
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def _prop_args(elements, times):
    return [jnp.asarray(v) for v in elements.arrays()] + [jnp.asarray(times)]


def _propagation(rows, report):
    n_sats = int(os.environ.get("ORBITS_BENCH_SATS", str(FULL_SATS)))
    n_steps = int(os.environ.get("ORBITS_BENCH_STEPS", str(FULL_STEPS)))
    times = np.arange(n_steps, dtype=np.float64) * 60.0
    els = walker_delta(n_sats, max(d for d in range(1, int(np.sqrt(n_sats)) + 1)
                                   if n_sats % d == 0), 53.0, 550.0)
    t = _time_s(propagate_jit, *_prop_args(els, times))
    tput = n_sats * n_steps / t
    report["propagation"] = {
        "n_sats": n_sats, "n_steps": n_steps, "seconds": t,
        "sat_steps_per_s": tput,
        "full_size": n_sats >= FULL_SATS and n_steps >= FULL_STEPS,
    }
    rows.append((f"orbits_prop_{n_sats}x{n_steps}", t * 1e6,
                 f"{tput / 1e6:.2f}M sat-steps/s one jitted program"))

    # the full-catalog screening shape (short grid: sizing, not horizon)
    cat_steps = min(n_steps, 90)
    cat = shell(CATALOG_SIZE, 53.0, 550.0)
    tc = _time_s(propagate_jit,
                 *_prop_args(cat, np.arange(cat_steps, dtype=np.float64)
                             * 60.0))
    report["propagation_catalog"] = {
        "n_sats": CATALOG_SIZE, "n_steps": cat_steps, "seconds": tc,
        "sat_steps_per_s": CATALOG_SIZE * cat_steps / tc,
        "ms_per_step_full_catalog": tc / cat_steps * 1e3,
    }
    rows.append((f"orbits_catalog_{CATALOG_SIZE}x{cat_steps}", tc * 1e6,
                 f"{tc / cat_steps * 1e3:.1f} ms per full-catalog step"))
    return times, els


def _visibility(rows, report, times, els):
    # memory-aware: the elevation grid is stations x sats x times f32 —
    # cap the sats/steps slab so smoke and full runs both fit easily
    n_st = int(os.environ.get("ORBITS_BENCH_STATIONS", "8"))
    n_sats = min(els.n_sats, 1024)
    n_steps = min(times.shape[0], FULL_STEPS)
    sub = shell(n_sats, 53.0, 550.0)
    t_grid = times[:n_steps]
    pos = propagate_jit(*_prop_args(sub, t_grid))
    pos.block_until_ready()
    sites = np.stack([station_ecef(*s) for s in default_sites(n_st)])

    tv = _time_s(lambda: elevation_deg(pos, t_grid, sites))
    report["visibility"] = {
        "n_stations": n_st, "n_sats": n_sats, "n_steps": n_steps,
        "seconds": tv,
        "station_sat_steps_per_s": n_st * n_sats * n_steps / tv,
    }
    rows.append((f"orbits_elev_{n_st}x{n_sats}x{n_steps}", tv * 1e6,
                 f"{n_st * n_sats * n_steps / tv / 1e6:.2f}M "
                 f"station-sat-steps/s"))

    elev = np.asarray(elevation_deg(pos, t_grid, sites))
    t0 = time.perf_counter()
    ps = extract_passes(elev, t_grid, 10.0)
    tp = time.perf_counter() - t0
    dur = np.sort(ps.duration_s)
    skew = (float(np.percentile(dur, 90) / max(np.median(dur), 1e-9))
            if ps.n_passes else 0.0)
    report["passes"] = {
        "seconds": tp, "n_passes": ps.n_passes,
        "duration_p50_s": float(np.median(dur)) if ps.n_passes else 0.0,
        "duration_p90_s": (float(np.percentile(dur, 90))
                           if ps.n_passes else 0.0),
        "duration_max_s": float(dur[-1]) if ps.n_passes else 0.0,
        "p90_over_p50": skew,
        "max_elev_p50_deg": (float(np.median(ps.max_elev_deg))
                             if ps.n_passes else 0.0),
        "max_elev_p90_deg": (float(np.percentile(ps.max_elev_deg, 90))
                             if ps.n_passes else 0.0),
    }
    rows.append((f"orbits_passes_{ps.n_passes}", tp * 1e6,
                 f"segment-scan extraction; p90/p50 duration "
                 f"{skew:.2f}x (skewed pass mix)"))

    te = _time_s(lambda: _eclipse_jit(pos, sun_direction(t_grid)))
    report["eclipse"] = {
        "n_sats": n_sats, "n_steps": n_steps, "seconds": te,
        "sat_steps_per_s": n_sats * n_steps / te,
    }
    rows.append((f"orbits_eclipse_{n_sats}x{n_steps}", te * 1e6,
                 "cylindrical shadow mask, one jitted program"))


def run(json_path: str = None):
    if json_path is None:
        json_path = os.environ.get("ORBITS_BENCH_JSON", JSON_PATH)
    rows, report = [], {}
    times, els = _propagation(rows, report)
    _visibility(rows, report, times, els)

    perf_on = _perf_gates_enforced()
    prop = report["propagation"]
    report["_summary"] = {
        "cpu_cores": os.cpu_count(),
        "perf_gates_enforced": perf_on,
        "sat_steps_per_s": prop["sat_steps_per_s"],
        "throughput_gate": THROUGHPUT_GATE,
        "gate_throughput": (prop["sat_steps_per_s"] >= THROUGHPUT_GATE
                            if prop["full_size"] and perf_on else None),
        "pass_skew_p90_over_p50": report["passes"]["p90_over_p50"],
    }
    rows.append(("orbits_summary", 0.0,
                 f"prop={prop['sat_steps_per_s'] / 1e6:.2f}M sat-steps/s "
                 f"gate={report['_summary']['gate_throughput']} "
                 f"skew={report['passes']['p90_over_p50']:.2f}x"))
    with open(json_path, "w") as f:
        json.dump(report, f, indent=2)
    # gates raise AFTER the report lands (run.py then exits nonzero)
    if report["_summary"]["gate_throughput"] is False:
        raise AssertionError(
            f"propagation throughput gate: "
            f"{prop['sat_steps_per_s'] / 1e6:.2f}M sat-steps/s < "
            f"{THROUGHPUT_GATE / 1e6:.2f}M at "
            f"{prop['n_sats']}x{prop['n_steps']} (see {json_path})")
    return rows
