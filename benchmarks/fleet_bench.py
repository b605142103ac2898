"""Constellation throughput: the vectorized Fleet engine vs the looped
sequential-Mission oracle, plus the device-mesh sharded-runtime sweep.

**Size sweep** — for each fleet size (default 2/8/32 satellites,
override with the ``FLEET_BENCH_SATS`` env var, e.g.
``FLEET_BENCH_SATS=2,8``), one deterministic multi-round scenario
(eclipse/sunlit harvest, rotating variable-bandwidth contact windows) is
generated ONCE and executed by both arms, so timing excludes scene
synthesis and the two arms consume byte-identical inputs. Each size runs
one untimed warm pass of BOTH arms and then interleaves the timed
iterations — the speedup measured is steady-state execution (shared
frame buckets + shared counting batches + the vmapped multi-sat dedup
core), not compile amortization, which benchmarks/pipeline_bench.py
already covers. The acceptance gate is
>= 1.25x over the loop at 8 satellites — recalibrated from the original
2x when size-tiered counting batches (`cascade._tier_batch`) sped up
the looped baseline's small per-satellite batches by ~2x: both arms got
faster in absolute terms, so the fleet's *relative* margin is
structurally smaller now (its remaining edge is shared frame buckets,
shared trailing-batch padding, and the single vmapped dedup call).

**Stations sweep** — the contact tier: a dense ground-segment scenario
(default 32 satellites x 8 stations per round, override with
``FLEET_BENCH_CONTACT_SATS`` / ``FLEET_BENCH_STATIONS`` or
``--stations N``) executed three ways over identical events — the
batched ContactPlan planner (lane-stacked select_batch + vectorized
ledger charges + shared recount batches), the scalar FIFO-loop
reference (one ``Mission.contact_window`` per window, the pre-plan
contact tier), and the async arm (``async_ground=True``: each round's
batched ground recount deferred to a worker thread that overlaps the
next round's ingest). Timed via the fleets' cumulative ``contact_s``
(best of interleaved iterations after a warm pass of every arm), so the
speedup is contact-tier-only and steady-state. Gates (full-size sweep
only, and ratio gates only on >= ``PERF_GATES_MIN_CORES``-core boxes;
parity always): batched >= 1.5x the looped reference; the async
arm hides >= 50% of recount wall time behind foreground work
(``recount_hidden_frac`` = 1 - sync-wait / recount); and all three
arms' per-tile predictions/summaries agree at 0.0 deviation.

**Depth sweep** — the bounded recount pipeline: the stations-sweep
scenario executed at every ``FLEET_BENCH_DEPTHS`` pipeline depth
(default 0/1/2 — synchronous, the single-slot overlap, and two rounds
in flight with backpressure). Per-depth contact wall and recount
accounting (``recount_s`` / ``recount_wait_s`` / ``hidden_frac``, best
across interleaved iterations), the ``wait_s <= recount_s`` accounting
invariant asserted per arm, a 0.0-deviation parity gate across ALL
depths (always enforced), and the depth-scaling gate — depth 2 hides at
least the recount fraction depth 1 hides (full-size sweeps on
>= ``PERF_GATES_MIN_CORES``-core boxes only, recorded always).

**Devices sweep** — the same fixed-size scenario (``FLEET_BENCH_SHARD_SATS``,
default 8 satellites) executed by the sharded fleet runtime at 1/2/4
devices (``FLEET_BENCH_DEVICES``), every arm in this one process on a
``sats`` mesh over the first N visible devices; arms asking for more
devices than are visible are recorded as skipped. On the CPU, several
devices come from ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
set before the run starts. Ingest runs the vmapped dedup core — no
per-satellite Python loop — at every device count. The parity gate:
per-tile predictions and per-sat summaries across ALL device counts
must match the single-device arm within ``SHARD_PARITY_TOL`` (0.0 — the
documented bit-equal-on-CPU dedup tolerance; ``run.py fleet``
turns a violation into a nonzero exit). On forced host devices the
"devices" share one CPU's cores, so sharded wall-clock mostly
demonstrates structure (real gains need real accelerators); the
recorded numbers are honest either way.

**Overlap sweep** — round-pipelined ingest: the size-sweep scenario
driven with contact rounds only every second round (back-to-back ingest
calls are what the deferred tail hides behind), executed with
``Fleet(ingest_overlap=...)`` off and on (``FLEET_BENCH_OVERLAP``,
default ``0,1``). Timed via the fleets' cumulative ``ingest_s`` (best
of interleaved iterations after a warm pass per arm). Gates: both arms'
per-tile predictions and summaries agree at 0.0 deviation (always);
the overlap arm hides >= ``INGEST_HIDE_GATE`` of its deferred-fetch
wall (``ingest_hidden_frac``) on full-size sweeps on
>= ``PERF_GATES_MIN_CORES``-core boxes. The churn gate rides along and
is enforced EVERYWHERE (it counts uploads, not wall time): a round
re-presenting the previous round's control arrays (gather indices,
lane/cluster vectors, dedup key stacks) must hit the content-keyed
transfer cache (``repro.core.xfer``) — i.e. issue strictly fewer
``device_put``s than the pre-cache engine, which paid
``device_puts + cache_reuses`` uploads for the identical work.

**Faults sweep** — the robustness tier: one scenario
(``FLEET_BENCH_FAULT_SATS``, default 8 satellites) executed under
deterministic fault injection at increasing fault rates
(``FLEET_BENCH_FAULT_RATES``, default 0/5/10/25% applied to window
drops and segment corruption, plus pinned corruption of round 0's
windows so corruption provably fires — and is provably re-served by
the rotation — at every nonzero rate), on the dense multi-window
scenario, recording detection error and contact throughput per rate.

**Orbital sweep** — the contact tier driven by the orbital geometry
engine (``geometry="orbital"``, ``FLEET_BENCH_ORBITAL_SATS``, default
16 satellites over the ``FLEET_BENCH_STATIONS`` site network): contact
windows come from extracted passes (elevation-priced bandwidth,
duration-integrated budgets — a heavy-tailed window mix, recorded as
budget p90/p50 skew) instead of the round-robin rotation. Batched
ContactPlan vs FIFO-loop reference, 0.0-deviation parity gate; set
``FLEET_BENCH_ORBITAL_SATS=0`` to disable. Three gates ride along: (1) the **disabled-path
overhead** of the fault subsystem — ``FaultPlan.none()`` vs
``faults=None`` — stays < 2% (full-size sweep only, and only when the
box's same-arm timing noise floor can resolve 2%; the parity of the
two arms is asserted always); (2) the **retry arm** (bounded
retry-with-backoff) recovers at least the no-retry arm's ground-kept
downlinked bytes at EVERY rate (identical fault draws via
``FaultPlan.with_retries``); (3) the **async watchdog arm** — an
injected ground-worker crash recovered by the watchdog — matches the
synchronous arm bit-exactly.

Writes ``BENCH_fleet.json``.
"""
from __future__ import annotations

import json
import os
import sys
import time

JSON_PATH = "BENCH_fleet.json"
DEFAULT_SATS = (2, 8, 32)
DEFAULT_DEVICES = (1, 2, 4)
DEFAULT_DEPTHS = (0, 1, 2)
DEFAULT_FAULT_RATES = (0.0, 0.05, 0.10, 0.25)
SHARD_PARITY_TOL = 0.0  # documented dedup tolerance: bit-equal on CPU
SPEEDUP_GATE = 1.25     # fleet vs loop at 8 sats (see module docstring)
CONTACT_PARITY_TOL = 0.0   # batched planner vs FIFO reference: bit-equal
CONTACT_SPEEDUP_GATE = 1.5  # batched vs looped contact tier, 32x8 sweep
ASYNC_HIDE_GATE = 0.5      # recount wall time hidden behind ingest
INGEST_HIDE_GATE = 0.3     # deferred ingest fetch wall hidden behind dispatch
SIZE_SPEEDUP_FLOOR = 1.0   # fleet vs loop at the largest size sweep
FAULT_OVERHEAD_GATE = 0.02  # FaultPlan.none() vs faults=None wall overhead
# The perf-RATIO gates (fleet speedup @8 sats, contact speedup, async
# hidden fraction, fault-off overhead) were calibrated on a multi-core
# runner: the batched/async arms win precisely by exploiting intra-op
# parallelism, so on a 1-core box the ratios are structurally different
# (and wall-clock noise can't resolve a 2% overhead bound at all). On
# such boxes every number is still measured and recorded — only the
# ratio-gate ENFORCEMENT is skipped (gate value null in the JSON, with
# cpu_cores/perf_gates_enforced recording why). Parity/robustness gates
# (0.0 deviation, retry recovery, watchdog bit-exactness) are machine-
# independent and always enforced.
PERF_GATES_MIN_CORES = 2


def _perf_gates_enforced() -> bool:
    return (os.cpu_count() or 1) >= PERF_GATES_MIN_CORES


def _ints_from_env(name, default):
    env = os.environ.get(name, "")
    if not env:
        return default
    return tuple(int(x) for x in env.replace(",", " ").split())


def _bench_knobs():
    return (int(os.environ.get("FLEET_BENCH_ROUNDS", "3")),
            int(os.environ.get("FLEET_BENCH_ITERS", "3")),
            int(os.environ.get("FLEET_BENCH_FRAMES", "1")))


def _spec_for(n_sats, seed):
    from repro.data.scenarios import FleetScenarioSpec, GroundStation
    from repro.data.synthetic import SceneSpec

    n_rounds, _, frames_per_pass = _bench_knobs()
    scene = SceneSpec("fleet", 384, (10, 20), (10, 24), cloud_fraction=0.25)
    return FleetScenarioSpec(
        n_sats=n_sats, n_rounds=n_rounds,
        frames_per_pass=frames_per_pass,
        stations=(GroundStation("gs0"),
                  GroundStation("gs1", bandwidth_mbps=30.0)),
        scene_mix=(scene,), seed=seed)


def _contact_spec(n_sats, n_stations, seed):
    """Dense ground-segment scenario: every round offers ``n_stations``
    rotating windows at staggered bandwidths, so pending passes pile up
    between a satellite's contacts and windows drain multi-segment."""
    from repro.data.scenarios import FleetScenarioSpec, GroundStation
    from repro.data.synthetic import SceneSpec

    n_rounds, _, frames_per_pass = _bench_knobs()
    scene = SceneSpec("contact", 384, (10, 20), (10, 24), cloud_fraction=0.25)
    stations = tuple(
        GroundStation(f"gs{k}", bandwidth_mbps=30.0 + 5.0 * (k % 5),
                      contact_s=240.0 + 30.0 * (k % 3))
        for k in range(n_stations))
    return FleetScenarioSpec(
        n_sats=n_sats, n_rounds=n_rounds, frames_per_pass=frames_per_pass,
        stations=stations, scene_mix=(scene,), seed=seed)


def _stations_sweep(rows, report):
    """Batched ContactPlan vs FIFO-loop reference vs async overlap (see
    module docstring). Returns the report row (None when disabled)."""
    import numpy as np

    from benchmarks.common import counters
    from repro.core.fleet import run_scenario
    from repro.core.pipeline import PipelineConfig
    from repro.data.scenarios import generate_scenario

    n_stations = int(os.environ.get("FLEET_BENCH_STATIONS", "8"))
    n_sats = int(os.environ.get("FLEET_BENCH_CONTACT_SATS", "32"))
    if n_stations <= 0:
        return None
    n_rounds, iters, _ = _bench_knobs()
    space, ground = counters()
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    sc = generate_scenario(_contact_spec(n_sats, n_stations, seed=6))

    def arm(**kw):
        return run_scenario(space, ground, pcfg, sc, fleet=True, **kw)

    arms = (("batched", {}), ("reference", {"contact_reference": True}),
            ("async", {"async_ground": True}))
    for _, kw in arms:  # warm: every compile (lane-stacked throttle,
        arm(**kw)       # per-depth select programs) lands untimed
    best, res_by = {}, {}
    for _ in range(iters):
        for name, kw in arms:  # interleaved: drift hits all arms evenly
            res, fl = arm(**kw)
            s = fl.summary()
            if name not in best or s["contact_s"] < best[name]["contact_s"]:
                best[name] = s
            res_by[name] = res

    max_dev = 0.0
    for name in ("reference", "async"):
        for a, b in zip(res_by["batched"], res_by[name]):
            if a.per_tile_pred.size:
                max_dev = max(max_dev, float(np.max(np.abs(
                    a.per_tile_pred - b.per_tile_pred))))
            assert a.summary() == b.summary(), \
                f"contact-plan {name} arm summary mismatch"
    sb, sr, sa = best["batched"], best["reference"], best["async"]
    speedup = sr["contact_s"] / sb["contact_s"]
    hidden = sa["recount_hidden_frac"]
    row = {
        "n_sats": n_sats, "stations": n_stations, "rounds": n_rounds,
        "windows_served": sb["windows_served"],
        "batched_contact_s": sb["contact_s"],
        "reference_contact_s": sr["contact_s"],
        "speedup": speedup,
        "windows_per_s": sb["windows_per_s"],
        "bytes_downlinked_per_s": sb["bytes_downlinked_per_s"],
        "async_contact_s": sa["contact_s"],
        "async_recount_s": sa["recount_s"],
        "async_recount_wait_s": sa["recount_wait_s"],
        "async_recount_hidden_frac": hidden,
        "pred_max_dev": max_dev,
        # perf gates apply to the full-size sweep only (smoke configs
        # shrink the scenario and measure structure, not throughput)
        "full_size": n_sats >= 32 and n_stations >= 8,
    }
    report[f"contact_{n_sats}sats_{n_stations}st"] = row
    rows.append((f"contact_{n_sats}sats_{n_stations}st",
                 sb["contact_s"] * 1e6,
                 f"speedup={speedup:.2f}x hidden={hidden:.2f} "
                 f"wps={sb['windows_per_s']:.1f} dev={max_dev:.1e}"))
    return row


def _depth_sweep(rows, report):
    """Bounded recount-pipeline depth sweep (``FLEET_BENCH_DEPTHS``,
    default 0,1,2) over the stations-sweep scenario: per-depth contact
    wall and recount accounting, a 0.0-deviation parity gate across
    every depth, the ``wait_s <= recount_s`` accounting invariant per
    arm, and the depth-scaling gate — depth 2 must hide at least the
    recount fraction depth 1 hides (full-size sweeps on
    >= ``PERF_GATES_MIN_CORES``-core boxes only; recorded always).
    Hidden fractions are the best (max) across iterations, matching the
    best-wall convention of the other arms."""
    import numpy as np

    from benchmarks.common import counters
    from repro.core.fleet import run_scenario
    from repro.core.pipeline import PipelineConfig
    from repro.data.scenarios import generate_scenario

    depths = tuple(_ints_from_env("FLEET_BENCH_DEPTHS", DEFAULT_DEPTHS))
    n_stations = int(os.environ.get("FLEET_BENCH_STATIONS", "8"))
    n_sats = int(os.environ.get("FLEET_BENCH_CONTACT_SATS", "32"))
    if not depths or n_stations <= 0:
        return None
    n_rounds, iters, _ = _bench_knobs()
    space, ground = counters()
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    sc = generate_scenario(_contact_spec(n_sats, n_stations, seed=6))

    def arm(depth):
        return run_scenario(space, ground, pcfg, sc, fleet=True,
                            async_depth=depth)

    for d in depths:
        arm(d)  # warm: compiles land untimed
    best, hidden, res_by = {}, {}, {}
    for _ in range(iters):
        for d in depths:  # interleaved: drift hits all depths evenly
            res, fl = arm(d)
            s = fl.summary()
            assert s["recount_wait_s"] <= s["recount_s"], (
                f"depth={d}: wait_s={s['recount_wait_s']} > "
                f"recount_s={s['recount_s']}")
            if d not in best or s["contact_s"] < best[d]["contact_s"]:
                best[d] = s
            hidden[d] = max(hidden.get(d, 0.0), s["recount_hidden_frac"])
            res_by[d] = res

    max_dev = 0.0
    base = res_by[depths[0]]
    for d in depths[1:]:
        for a, b in zip(base, res_by[d]):
            if a.per_tile_pred.size:
                max_dev = max(max_dev, float(np.max(np.abs(
                    a.per_tile_pred - b.per_tile_pred))))
            assert a.summary() == b.summary(), \
                f"depth sweep: depth={d} summary mismatch vs depth={depths[0]}"
    row = {
        "n_sats": n_sats, "stations": n_stations, "rounds": n_rounds,
        "depths": list(depths),
        "pred_max_dev": max_dev,
        "full_size": n_sats >= 32 and n_stations >= 8,
        "per_depth": {
            str(d): {
                "contact_s": best[d]["contact_s"],
                "recount_s": best[d]["recount_s"],
                "recount_wait_s": best[d]["recount_wait_s"],
                "hidden_frac": hidden[d],
                "max_in_flight": best[d]["recount_max_in_flight"],
            } for d in depths},
    }
    report["depth_sweep"] = row
    frac = " ".join(f"d{d}={hidden[d]:.2f}" for d in depths)
    rows.append(("depth_sweep",
                 best[depths[-1]]["contact_s"] * 1e6,
                 f"hidden: {frac} dev={max_dev:.1e}"))
    return row


def _orbital_spec(n_sats, n_stations, seed):
    """The stations-sweep scenario re-based on real orbital geometry:
    contacts come from extracted passes over a globally dispersed site
    network (heavy-tailed pass mix — many low-elevation grazes, few
    long overhead passes), harvest grants from eclipse fractions."""
    from repro.data.scenarios import FleetScenarioSpec, GroundStation
    from repro.data.synthetic import SceneSpec
    from repro.orbits.schedule import default_sites

    n_rounds, _, frames_per_pass = _bench_knobs()
    scene = SceneSpec("orbital", 384, (10, 20), (10, 24), cloud_fraction=0.25)
    sites = default_sites(n_stations)
    stations = tuple(
        GroundStation(f"gs{k}", bandwidth_mbps=30.0 + 5.0 * (k % 5),
                      contact_s=240.0 + 30.0 * (k % 3), site=sites[k])
        for k in range(n_stations))
    return FleetScenarioSpec(
        n_sats=n_sats, n_rounds=n_rounds, frames_per_pass=frames_per_pass,
        stations=stations, scene_mix=(scene,), seed=seed,
        geometry="orbital", min_elev_deg=5.0)


def _orbital_sweep(rows, report):
    """The contact tier fed by the orbital geometry engine: batched
    ContactPlan vs FIFO-loop reference over pass-derived windows.
    Parity gate always (0.0 deviation); the interesting report numbers
    are the pass-mix skew the extracted schedule exhibits."""
    import numpy as np

    from benchmarks.common import counters
    from repro.core.fleet import run_scenario
    from repro.core.pipeline import PipelineConfig
    from repro.data.scenarios import generate_scenario

    n_sats = int(os.environ.get("FLEET_BENCH_ORBITAL_SATS", "16"))
    n_stations = int(os.environ.get("FLEET_BENCH_STATIONS", "8"))
    if n_sats <= 0 or n_stations <= 0:
        return None
    n_rounds, iters, _ = _bench_knobs()
    space, ground = counters()
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    sc = generate_scenario(_orbital_spec(n_sats, n_stations, seed=6))
    budgets = np.array([c.budget_bytes
                        for r in sc.rounds for c in r.contacts])
    n_windows = budgets.size

    def arm(**kw):
        return run_scenario(space, ground, pcfg, sc, fleet=True, **kw)

    arms = (("batched", {}), ("reference", {"contact_reference": True}))
    for _, kw in arms:
        arm(**kw)
    best, res_by = {}, {}
    for _ in range(iters):
        for name, kw in arms:
            res, fl = arm(**kw)
            s = fl.summary()
            if name not in best or s["contact_s"] < best[name]["contact_s"]:
                best[name] = s
            res_by[name] = res

    max_dev = 0.0
    for a, b in zip(res_by["batched"], res_by["reference"]):
        if a.per_tile_pred.size:
            max_dev = max(max_dev, float(np.max(np.abs(
                a.per_tile_pred - b.per_tile_pred))))
        assert a.summary() == b.summary(), \
            "orbital contact reference arm summary mismatch"
    sb = best["batched"]
    row = {
        "n_sats": n_sats, "stations": n_stations, "rounds": n_rounds,
        "geometry": "orbital",
        "n_windows": int(n_windows),
        "windows_served": sb["windows_served"],
        "batched_contact_s": sb["contact_s"],
        "reference_contact_s": best["reference"]["contact_s"],
        "budget_p50_bytes": float(np.median(budgets)) if n_windows else 0.0,
        "budget_p90_bytes": (float(np.percentile(budgets, 90))
                             if n_windows else 0.0),
        "budget_skew_p90_over_p50": (
            float(np.percentile(budgets, 90) / max(np.median(budgets), 1e-9))
            if n_windows else 0.0),
        "pred_max_dev": max_dev,
    }
    report[f"orbital_{n_sats}sats_{n_stations}st"] = row
    rows.append((f"fleet_orbital_{n_sats}sats_{n_stations}st",
                 sb["contact_s"] * 1e6,
                 f"windows={n_windows} "
                 f"skew={row['budget_skew_p90_over_p50']:.2f}x "
                 f"dev={max_dev:.1e}"))
    return row


def _overlap_sweep(rows, report):
    """Round-pipelined ingest arms (module docstring): overlap off vs
    on over identical rounds, parity at 0.0 always, plus the
    count-based transfer-cache churn gate. Returns the row (None when
    disabled)."""
    import numpy as np

    from benchmarks.common import counters
    from repro.core import xfer
    from repro.core.fleet import Fleet
    from repro.core.pipeline import PipelineConfig
    from repro.data.scenarios import generate_scenario

    arms = tuple(int(x) for x in os.environ.get(
        "FLEET_BENCH_OVERLAP", "0,1").replace(",", " ").split())
    n_sats = int(os.environ.get("FLEET_BENCH_OVERLAP_SATS", "32"))
    if not arms or n_sats <= 0:
        return None
    n_rounds, iters, _ = _bench_knobs()
    space, ground = counters()
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    sc = generate_scenario(_spec_for(n_sats, seed=9))

    def drive(overlap):
        fl = Fleet(space, ground, pcfg, n_sats=n_sats,
                   ingest_overlap=bool(overlap))
        for k, rnd in enumerate(sc.rounds):
            fl.ingest(rnd.frames_per_sat(n_sats),
                      rnd.harvest_per_sat(n_sats))
            # contact only every second round: consecutive ingest
            # rounds are exactly what the deferred tail hides behind
            if rnd.contacts and k % 2 == 1:
                fl.contact_round(plan=rnd.contact_plan(n_sats))
        res = fl.finalize()
        return res, fl.summary()

    for ov in arms:
        drive(ov)  # warm: compiles land untimed
    best, res_by = {}, {}
    for _ in range(iters):
        for ov in arms:  # interleaved: drift hits both arms evenly
            res, s = drive(ov)
            if ov not in best or s["ingest_s"] < best[ov]["ingest_s"]:
                best[ov] = s
            res_by[ov] = res

    max_dev = 0.0
    base = res_by[arms[0]]
    for ov in arms[1:]:
        for a, b in zip(base, res_by[ov]):
            if a.per_tile_pred.size:
                max_dev = max(max_dev, float(np.max(np.abs(
                    a.per_tile_pred - b.per_tile_pred))))
            assert a.summary() == b.summary(), \
                f"ingest overlap={ov} arm summary mismatch"

    # -- churn gate: repeat-round upload counts through the xfer cache ----
    churn_sats = min(n_sats, 8)
    churn_sc = (sc if churn_sats == n_sats
                else generate_scenario(_spec_for(churn_sats, seed=9)))
    fl = Fleet(space, ground, pcfg, n_sats=churn_sats)
    rnd = churn_sc.rounds[0]
    frames = rnd.frames_per_sat(fl.n_sats)
    harvest = rnd.harvest_per_sat(fl.n_sats)
    xfer.clear_cache()
    xfer.reset_transfer_stats()
    fl.ingest(frames, harvest)
    first = xfer.transfer_stats()
    xfer.reset_transfer_stats()
    fl.ingest(frames, harvest)
    repeat = xfer.transfer_stats()
    pre_cache = repeat["device_puts"] + repeat["cache_reuses"]

    son = best.get(1) or best.get(arms[-1])
    soff = best.get(0) or best.get(arms[0])
    hidden = son["ingest_hidden_frac"] if son else None
    speedup = (soff["ingest_s"] / son["ingest_s"]
               if son and soff and son is not soff else None)
    row = {
        "n_sats": n_sats, "rounds": n_rounds, "arms": list(arms),
        "ingest_s_off": soff["ingest_s"] if soff else None,
        "ingest_s_on": son["ingest_s"] if son else None,
        "ingest_speedup": speedup,
        "ingest_hidden_frac": hidden,
        "ingest_dispatch_s": son["ingest_dispatch_s"] if son else None,
        "device_compute_s": son["device_compute_s"] if son else None,
        "host_fetch_s": son["host_fetch_s"] if son else None,
        "rounds_deferred": son["ingest_rounds_deferred"] if son else None,
        "pred_max_dev": max_dev,
        "first_round_device_puts": first["device_puts"],
        "repeat_round_device_puts": repeat["device_puts"],
        "repeat_round_cache_reuses": repeat["cache_reuses"],
        "pre_cache_round_puts": pre_cache,
        "transfer_saved_frac": (repeat["cache_reuses"] / pre_cache
                                if pre_cache else 0.0),
        "full_size": n_sats >= 32,
    }
    report["ingest_overlap"] = row
    rows.append(("ingest_overlap",
                 (son["ingest_s"] if son else 0.0) * 1e6,
                 f"speedup={speedup if speedup is None else round(speedup, 2)}"
                 f"x hidden={hidden} dev={max_dev:.1e} "
                 f"xfer={repeat['device_puts']}/{pre_cache}"))
    return row


def _jitguard_sweep(rows, report):
    """Runtime jit-recompilation sanitizer: drive identical fleet
    ingest rounds under :class:`repro.analysis.JitGuard` and record the
    XLA compilations each round triggers. Round 1 traces and compiles
    the programs; every later round re-presents bit-identical shapes,
    so rounds >= 2 must compile ZERO new programs. Count-based and
    machine-independent (like the transfer-cache churn gate), so the
    gate is enforced everywhere — a single recompile in steady state is
    the shape-churn class PR 9 eliminated. ``FLEET_BENCH_JITGUARD_SATS=0``
    disables."""
    from benchmarks.common import counters
    from repro.analysis.jitguard import JitGuard
    from repro.core.fleet import Fleet
    from repro.core.pipeline import PipelineConfig
    from repro.data.scenarios import generate_scenario

    n_sats = int(os.environ.get("FLEET_BENCH_JITGUARD_SATS", "4"))
    n_rounds = max(2, int(os.environ.get("FLEET_BENCH_JITGUARD_ROUNDS", "4")))
    if n_sats <= 0:
        return None
    space, ground = counters()
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    sc = generate_scenario(_spec_for(n_sats, seed=9))
    rnd = sc.rounds[0]
    frames = rnd.frames_per_sat(n_sats)
    harvest = rnd.harvest_per_sat(n_sats)
    fl = Fleet(space, ground, pcfg, n_sats=n_sats)

    per_round = []
    for k in range(n_rounds):
        with JitGuard(f"fleet round {k + 1}") as g:
            fl.ingest(frames, harvest)
        per_round.append(g.compilations)
    fl.finalize()

    steady = sum(per_round[1:])
    row = {
        "n_sats": n_sats, "rounds": n_rounds,
        "recompiles_per_round": per_round,
        "warmup_round_compiles": per_round[0],
        "steady_rounds_compiles": steady,
    }
    report["jitguard"] = row
    rows.append(("fleet_jitguard", 0.0,
                 f"warmup={per_round[0]} steady={steady}"))
    return row


def _floats_from_env(name, default):
    env = os.environ.get(name, "")
    if not env:
        return default
    return tuple(float(x) for x in env.replace(",", " ").split())


def _faults_sweep(rows, report):
    """Fault-injection sweep + the robustness gates (module docstring).
    Returns the summary dict (None when disabled)."""
    import numpy as np

    from benchmarks.common import counters
    from repro.core.faults import FaultPlan
    from repro.core.fleet import run_scenario
    from repro.core.pipeline import PipelineConfig
    from repro.data.scenarios import generate_scenario

    rates = _floats_from_env("FLEET_BENCH_FAULT_RATES", DEFAULT_FAULT_RATES)
    n_sats = int(os.environ.get("FLEET_BENCH_FAULT_SATS", "8"))
    if not rates or n_sats <= 0:
        return None
    n_rounds, iters, _ = _bench_knobs()
    space, ground = counters()
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    # the DENSE scenario (4 windows/round), not the 2-station one: retry
    # re-delivery needs a satellite to be served AGAIN after its failed
    # transmission — with one window per sat per scenario the retry and
    # no-retry arms are indistinguishable (recovery would only happen at
    # the zero-byte finalize flush, which transmits nothing)
    n_stations = min(4, max(1, n_sats // 2))
    sc = generate_scenario(_contact_spec(n_sats, n_stations, seed=8))
    full_size = n_sats >= 8

    def arm(**kw):
        return run_scenario(space, ground, pcfg, sc, fleet=True, **kw)

    # -- disabled-path overhead: FaultPlan.none() vs faults=None ----------
    # a 2% bound needs a stabler estimator than best-of-``iters``: run
    # more interleaved reps, take best-of each arm, and derive a noise
    # floor from the SAME-arm spread (best vs second-best of the off
    # arm) — when one arm against itself varies by more than the gate,
    # the box cannot resolve the bound and enforcement is skipped
    reps = max(iters, 5)
    res_off, _ = arm()                              # untimed warm runs
    res_none, _ = arm(faults=FaultPlan.none())
    for a, b in zip(res_off, res_none):  # parity always, 0.0 deviation
        np.testing.assert_array_equal(a.per_tile_pred, b.per_tile_pred)
        assert a.summary() == b.summary(), \
            "FaultPlan.none() arm diverged from faults=None"
    ts_off, ts_none = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        arm()
        t1 = time.perf_counter()
        arm(faults=FaultPlan.none())
        ts_off.append(t1 - t0)
        ts_none.append(time.perf_counter() - t1)
    t_off, t_none = min(ts_off), min(ts_none)
    overhead = t_none / t_off - 1.0
    noise_floor = sorted(ts_off)[1] / t_off - 1.0
    overhead_resolvable = noise_floor < FAULT_OVERHEAD_GATE

    # -- fault-rate sweep: retry vs no-retry arms over identical draws ----
    # every nonzero-rate plan also PINS corruption at pos 0 of round 0's
    # windows: rate-drawn sites can land on lanes that never transmit
    # (energy-starved sats, empty selections), and a corruption that
    # never fires would make the retry-vs-no-retry comparison vacuous.
    # Round 0 specifically, so the rotation re-serves the failed
    # satellite within the scenario and the retry arm's re-transmission
    # actually lands (not just the zero-byte finalize flush)
    pinned = frozenset((0, w, 0) for w in range(n_stations))
    per_rate = []
    for rate in rates:
        fp = FaultPlan(seed=17, drop_rate=rate, corrupt_rate=rate,
                       segment_corruptions=pinned if rate else frozenset(),
                       max_retries=2)
        res_r, fl_r = arm(faults=fp)
        res_n, fl_n = arm(faults=fp.with_retries(0))
        sr, sn = fl_r.summary(), fl_n.summary()

        def _err(res):
            pred = sum(r.total_pred for r in res)
            true = sum(r.total_true for r in res)
            return abs(pred - true) / max(true, 1.0)

        row = {
            "rate": rate,
            "detection_rel_err": _err(res_r),
            "detection_rel_err_no_retry": _err(res_n),
            "windows_per_s": sr["windows_per_s"],
            "windows_dropped": sr["fault_windows_dropped"],
            "segments_corrupted": sr["fault_segments_corrupted"],
            "segments_lost": sr["fault_segments_lost"],
            "bytes_delivered": sr["fault_bytes_delivered"],
            "bytes_delivered_no_retry": sn["fault_bytes_delivered"],
            "retry_recovers": (sr["fault_bytes_delivered"]
                               >= sn["fault_bytes_delivered"]),
        }
        per_rate.append(row)
        report[f"faults_rate_{int(rate * 100)}pct"] = row
        rows.append((f"faults_rate_{int(rate * 100)}pct",
                     sr["contact_s"] * 1e6,
                     f"err={row['detection_rel_err']:.3f} "
                     f"wps={row['windows_per_s']:.1f} "
                     f"lost={row['segments_lost']} "
                     f"recovered={row['retry_recovers']}"))

    # -- async watchdog arm: injected worker crash, bit-exact recovery ----
    fp_crash = FaultPlan(seed=17, drop_rate=0.1, corrupt_rate=0.1,
                         worker_faults={0: "crash"})
    res_w, fl_w = arm(faults=fp_crash, async_ground=True, watchdog_s=10.0)
    res_s, _ = arm(faults=fp_crash)
    watchdog_dev = 0.0
    for a, b in zip(res_w, res_s):
        if a.per_tile_pred.size:
            watchdog_dev = max(watchdog_dev, float(np.max(np.abs(
                a.per_tile_pred - b.per_tile_pred))))
        assert a.summary() == b.summary(), \
            "watchdog arm summary diverged from the synchronous arm"
    sw = fl_w.summary()

    out = {
        "n_sats": n_sats, "rounds": n_rounds, "rates": list(rates),
        "none_plan_overhead": overhead,
        "overhead_noise_floor": noise_floor,
        "overhead_resolvable": overhead_resolvable,
        "no_faults_s": t_off, "none_plan_s": t_none,
        "retry_recovers_all_rates": all(r["retry_recovers"]
                                        for r in per_rate),
        "watchdog_pred_max_dev": watchdog_dev,
        "watchdog_recoveries": sw["fault_watchdog_recoveries"],
        "worker_crashes": sw["fault_worker_crashes"],
        "full_size": full_size,
    }
    report["faults"] = out
    rows.append(("faults_summary", t_none * 1e6,
                 f"overhead={overhead:+.3f} "
                 f"noise={noise_floor:+.3f} "
                 f"recovers={out['retry_recovers_all_rates']} "
                 f"watchdog_dev={watchdog_dev:.1e}"))
    return out


def _best(fn, iters):
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn()
        ts.append(time.perf_counter() - t0)
    return min(ts), out


def _best_pair(fn_a, fn_b, iters):
    """Best-of-``iters`` for two arms with INTERLEAVED iterations, after
    one untimed warm run of each — machine-speed drift hits both arms
    evenly, and per-size compiles (the stacked fleet cores specialize on
    lane count) never land in a timed iteration."""
    out_a = fn_a()
    out_b = fn_b()
    ts_a, ts_b = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        out_a = fn_a()
        ts_a.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        out_b = fn_b()
        ts_b.append(time.perf_counter() - t0)
    return min(ts_a), out_a, min(ts_b), out_b


def _devices_arm(n_devices: int) -> dict:
    """Run the sharded arm on the first ``n_devices`` devices; timings
    + per-tile predictions."""
    import numpy as np

    from benchmarks.common import counters
    from repro.core.fleet import run_scenario
    from repro.core.fleet_sharding import sats_mesh
    from repro.core.pipeline import PipelineConfig
    from repro.data.scenarios import generate_scenario

    n_sats = int(os.environ.get("FLEET_BENCH_SHARD_SATS", "8"))
    _, iters, _ = _bench_knobs()
    mesh = sats_mesh(n_devices)  # None at 1 device = unsharded fleet
    space, ground = counters()
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)

    sc = generate_scenario(_spec_for(n_sats, seed=5))
    # warm on the exact scenario: every compile (incl. the lane-count-
    # specialized stacked cores) lands before the timed iterations
    run_scenario(space, ground, pcfg, sc, fleet=True, mesh=mesh)
    t, (res, fleet) = _best(
        lambda: run_scenario(space, ground, pcfg, sc, fleet=True, mesh=mesh),
        iters)
    summary = fleet.summary()
    return {
        "n_devices": n_devices,
        "fleet_s": t,
        "tiles": int(sum(r.tiles_total for r in res)),
        "dedup_batched": summary["dedup_batched"],
        "tiles_per_s": summary["tiles_per_s"],
        "preds": [np.asarray(r.per_tile_pred).tolist() for r in res],
        "summaries": [r.summary() for r in res],
    }


def _size_sweep(rows, report):
    import numpy as np

    from benchmarks.common import counters
    from repro.core.fleet import run_scenario
    from repro.core.pipeline import PipelineConfig
    from repro.data.scenarios import generate_scenario

    space, ground = counters()
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    _, iters, _ = _bench_knobs()

    for n_sats in _ints_from_env("FLEET_BENCH_SATS", DEFAULT_SATS):
        sc = generate_scenario(_spec_for(n_sats, seed=5))
        t_fleet, (res_f, fleet), t_loop, (res_l, _) = _best_pair(
            lambda: run_scenario(space, ground, pcfg, sc, fleet=True),
            lambda: run_scenario(space, ground, pcfg, sc, fleet=False),
            iters)
        max_dev = 0.0
        for a, b in zip(res_f, res_l):
            if a.per_tile_pred.size:
                max_dev = max(max_dev, float(np.max(np.abs(
                    a.per_tile_pred - b.per_tile_pred))))
            assert a.summary() == b.summary(), "fleet/loop summary mismatch"
        tiles = sum(r.tiles_total for r in res_f)
        speedup = t_loop / t_fleet
        fs = fleet.summary()
        report[f"sats_{n_sats}"] = {
            "n_sats": n_sats, "rounds": _bench_knobs()[0],
            "frames_per_pass": _bench_knobs()[2], "tiles": tiles,
            "fleet_s": t_fleet, "loop_s": t_loop, "speedup": speedup,
            "fleet_tiles_per_s": tiles / t_fleet,
            "fleet_tiles_per_s_per_sat": tiles / t_fleet / n_sats,
            "loop_tiles_per_s": tiles / t_loop,
            "dedup_batched": fs["dedup_batched"],
            "pred_max_dev": max_dev,
        }
        rows.append((f"fleet_{n_sats}sats", t_fleet * 1e6,
                     f"speedup={speedup:.2f}x tps={tiles / t_fleet:.0f} "
                     f"tps/sat={tiles / t_fleet / n_sats:.0f} "
                     f"dev={max_dev:.1e}"))


def _devices_sweep(rows, report):
    import numpy as np

    devices = _ints_from_env("FLEET_BENCH_DEVICES", DEFAULT_DEVICES)
    if not devices:
        return None
    if 1 not in devices:
        # the parity gate and speedup_vs_1dev are defined against the
        # single-device arm — always run it, whatever the env asked for
        devices = (1, *devices)
    import jax
    visible = len(jax.devices())
    skipped = sorted(d for d in set(devices) if d > visible)
    if skipped:
        report["devices_skipped"] = {"requested": skipped,
                                     "visible": visible}
    arms = [_devices_arm(d) for d in sorted(set(devices)) if d <= visible]
    base = arms[0]
    max_dev = 0.0
    for arm in arms:
        assert arm["dedup_batched"], \
            "sharded arm fell back to the per-sat dedup loop"
        for p_base, p_arm, s_base, s_arm in zip(
                base["preds"], arm["preds"],
                base["summaries"], arm["summaries"]):
            if p_base:
                max_dev = max(max_dev, float(np.max(np.abs(
                    np.asarray(p_base) - np.asarray(p_arm)))))
            assert s_base == s_arm, (
                f"per-sat summary mismatch between devices="
                f"{base['n_devices']} and devices={arm['n_devices']}")
    base_t = base["fleet_s"]
    for arm in arms:
        d = arm["n_devices"]
        report[f"devices_{d}"] = {
            "n_devices": d,
            "n_sats": int(os.environ.get("FLEET_BENCH_SHARD_SATS", "8")),
            "fleet_s": arm["fleet_s"],
            "tiles": arm["tiles"],
            "tiles_per_s": arm["tiles"] / arm["fleet_s"],
            "speedup_vs_1dev": base_t / arm["fleet_s"],
            "dedup_batched": arm["dedup_batched"],
        }
        rows.append((f"fleet_devices_{d}", arm["fleet_s"] * 1e6,
                     f"tps={arm['tiles'] / arm['fleet_s']:.0f} "
                     f"vs1dev={base_t / arm['fleet_s']:.2f}x"))
    return max_dev


def run(json_path: str = None):
    if json_path is None:
        # smoke configs redirect the report (FLEET_BENCH_JSON) so tiny
        # CI runs never clobber the committed BENCH_fleet.json
        json_path = os.environ.get("FLEET_BENCH_JSON", JSON_PATH)
    rows, report = [], {}
    _size_sweep(rows, report)
    contact = _stations_sweep(rows, report)
    depth = _depth_sweep(rows, report)
    orbital = _orbital_sweep(rows, report)
    overlap = _overlap_sweep(rows, report)
    jitg = _jitguard_sweep(rows, report)
    faults = _faults_sweep(rows, report)
    shard_dev = _devices_sweep(rows, report)

    perf_on = _perf_gates_enforced()
    report["_summary"] = {
        "cpu_cores": os.cpu_count(),
        "perf_gates_enforced": perf_on,
        "speedup_at_8_sats": report.get("sats_8", {}).get("speedup"),
        "speedup_gate": SPEEDUP_GATE,
        "gate_speedup_at_8_sats": (report["sats_8"]["speedup"] >= SPEEDUP_GATE
                                   if "sats_8" in report and perf_on
                                   else None),
        "speedup_at_32_sats": report.get("sats_32", {}).get("speedup"),
        "gate_speedup_at_32_sats": (
            report["sats_32"]["speedup"] > SIZE_SPEEDUP_FLOOR
            if "sats_32" in report and perf_on else None),
        # the PR-8-era 0.99x at 32 sats, diagnosed while building the
        # transfer-count instrumentation this sweep now carries:
        "sats_32_root_cause": (
            "per-round churn, not batching: each of the 32-sat rounds "
            "re-uploaded bit-identical control arrays (counting gather "
            "indices, dedup lane/cluster vectors and PRNG key stacks), "
            "rebuilt NamedSharding placements, materialized full frames "
            "just to read .shape, and blocked on fleet-wide "
            "device->host syncs (roi_std, dedup assignments, counting "
            "results, the energy-cap round-trip) between every round's "
            "dispatch. The churn grows with fleet size while the looped "
            "baseline pays none of it, so on a 1-core runner it erased "
            "the batching margin at 32 sats. Eliminated by the "
            "content-keyed transfer cache (repro.core.xfer), cached "
            "mesh placements (FleetSharding.placement), np.shape frame "
            "probes, and the ingest_overlap deferred-fetch tail."),
        "max_pred_dev": max(r["pred_max_dev"] for k, r in report.items()
                            if k.startswith("sats_")),
        "sharded_pred_max_dev": shard_dev,
        "shard_parity_tol": SHARD_PARITY_TOL,
        "contact_speedup": contact["speedup"] if contact else None,
        "contact_speedup_gate": CONTACT_SPEEDUP_GATE,
        "gate_contact_speedup": (
            contact["speedup"] >= CONTACT_SPEEDUP_GATE
            if contact and contact["full_size"] and perf_on else None),
        "contact_pred_max_dev": (contact["pred_max_dev"]
                                 if contact else None),
        "contact_parity_tol": CONTACT_PARITY_TOL,
        "orbital_pred_max_dev": (orbital["pred_max_dev"]
                                 if orbital else None),
        "orbital_budget_skew": (orbital["budget_skew_p90_over_p50"]
                                if orbital else None),
        "async_recount_hidden_frac": (
            contact["async_recount_hidden_frac"] if contact else None),
        "async_hide_gate": ASYNC_HIDE_GATE,
        "gate_async_hidden": (
            contact["async_recount_hidden_frac"] >= ASYNC_HIDE_GATE
            if contact and contact["full_size"] and perf_on else None),
        "ingest_overlap_speedup": (overlap["ingest_speedup"]
                                   if overlap else None),
        "ingest_hidden_frac": (overlap["ingest_hidden_frac"]
                               if overlap else None),
        "ingest_hide_gate": INGEST_HIDE_GATE,
        "gate_ingest_hidden": (
            overlap["ingest_hidden_frac"] >= INGEST_HIDE_GATE
            if overlap and overlap["ingest_hidden_frac"] is not None
            and overlap["full_size"] and perf_on else None),
        "ingest_overlap_pred_max_dev": (overlap["pred_max_dev"]
                                        if overlap else None),
        "transfer_repeat_round_puts": (overlap["repeat_round_device_puts"]
                                       if overlap else None),
        "transfer_pre_cache_puts": (overlap["pre_cache_round_puts"]
                                    if overlap else None),
        "transfer_saved_frac": (overlap["transfer_saved_frac"]
                                if overlap else None),
        # count-based, so machine-independent: enforced EVERYWHERE
        "gate_transfer_cache": (
            overlap["repeat_round_device_puts"]
            < overlap["pre_cache_round_puts"] if overlap else None),
        "jit_recompiles_per_round": (jitg["recompiles_per_round"]
                                     if jitg else None),
        "jit_steady_rounds_compiles": (jitg["steady_rounds_compiles"]
                                       if jitg else None),
        # count-based, so machine-independent: enforced EVERYWHERE
        # (null only when disabled)
        "gate_jit_steady_state": (
            jitg["steady_rounds_compiles"] == 0 if jitg else None),
        "depth_pred_max_dev": depth["pred_max_dev"] if depth else None,
        "depth_hidden_fracs": (
            {d: v["hidden_frac"] for d, v in depth["per_depth"].items()}
            if depth else None),
        "gate_depth2_hidden_ge_depth1": (
            depth["per_depth"]["2"]["hidden_frac"]
            >= depth["per_depth"]["1"]["hidden_frac"]
            if depth and "1" in depth["per_depth"]
            and "2" in depth["per_depth"]
            and depth["full_size"] and perf_on else None),
        "fault_none_plan_overhead": (faults["none_plan_overhead"]
                                     if faults else None),
        "fault_overhead_gate": FAULT_OVERHEAD_GATE,
        "gate_fault_overhead": (
            faults["none_plan_overhead"] < FAULT_OVERHEAD_GATE
            if faults and faults["full_size"] and perf_on
            and faults["overhead_resolvable"] else None),
        "gate_fault_retry_recovers": (faults["retry_recovers_all_rates"]
                                      if faults else None),
        "fault_watchdog_pred_max_dev": (faults["watchdog_pred_max_dev"]
                                        if faults else None),
    }
    rows.append(("fleet_summary", 0.0,
                 f"speedup@8={report['_summary']['speedup_at_8_sats']} "
                 f"contact={report['_summary']['contact_speedup']} "
                 f"hidden={report['_summary']['async_recount_hidden_frac']} "
                 f"max_dev={report['_summary']['max_pred_dev']:.1e} "
                 f"shard_dev={shard_dev}"))
    with open(json_path, "w") as f:
        json.dump(report, f, indent=2)
    # fail loudly AFTER the report lands on disk (run.py turns
    # any gate into a nonzero exit); smoke configs without an 8-sat row
    # or a full-size contact sweep skip the perf gates by design, and so
    # do sub-``PERF_GATES_MIN_CORES`` boxes (gate value null, see the
    # constant's comment) — parity/robustness gates always apply
    if shard_dev is not None and shard_dev > SHARD_PARITY_TOL:
        raise AssertionError(
            f"sharded parity gate: pred_max_dev={shard_dev:.3e} exceeds "
            f"the documented dedup tolerance {SHARD_PARITY_TOL} across "
            f"the device sweep (see {json_path})")
    if contact and contact["pred_max_dev"] > CONTACT_PARITY_TOL:
        raise AssertionError(
            f"contact-plan parity gate: pred_max_dev="
            f"{contact['pred_max_dev']:.3e} exceeds "
            f"{CONTACT_PARITY_TOL} across batched/reference/async arms "
            f"(see {json_path})")
    if orbital and orbital["pred_max_dev"] > CONTACT_PARITY_TOL:
        raise AssertionError(
            f"orbital contact parity gate: pred_max_dev="
            f"{orbital['pred_max_dev']:.3e} exceeds {CONTACT_PARITY_TOL} "
            f"between batched and reference arms on the pass-derived "
            f"schedule (see {json_path})")
    if report["_summary"]["gate_speedup_at_8_sats"] is False:
        raise AssertionError(
            f"fleet speedup gate: {report['sats_8']['speedup']:.2f}x < "
            f"{SPEEDUP_GATE}x at 8 satellites (see {json_path})")
    if report["_summary"]["gate_speedup_at_32_sats"] is False:
        raise AssertionError(
            f"fleet size-scaling gate: {report['sats_32']['speedup']:.2f}x "
            f"<= {SIZE_SPEEDUP_FLOOR}x at 32 satellites — per-round churn "
            f"is back (see sats_32_root_cause in {json_path})")
    if overlap and overlap["pred_max_dev"] > CONTACT_PARITY_TOL:
        raise AssertionError(
            f"ingest-overlap parity gate: pred_max_dev="
            f"{overlap['pred_max_dev']:.3e} exceeds {CONTACT_PARITY_TOL} "
            f"between overlap arms (see {json_path})")
    if report["_summary"]["gate_transfer_cache"] is False:
        raise AssertionError(
            f"transfer-cache churn gate: a repeat round issued "
            f"{overlap['repeat_round_device_puts']} device_puts, not fewer "
            f"than the pre-cache engine's "
            f"{overlap['pre_cache_round_puts']} (see {json_path})")
    if report["_summary"]["gate_jit_steady_state"] is False:
        raise AssertionError(
            f"jit steady-state gate: rounds >= 2 of an identical-shape "
            f"fleet ingest compiled "
            f"{jitg['steady_rounds_compiles']} new XLA program(s) "
            f"(per-round {jitg['recompiles_per_round']}) — shape churn "
            f"is back; every steady-state round must hit the jit cache "
            f"(see {json_path})")
    if report["_summary"]["gate_ingest_hidden"] is False:
        raise AssertionError(
            f"ingest overlap gate: hidden fraction "
            f"{overlap['ingest_hidden_frac']:.2f} < {INGEST_HIDE_GATE} of "
            f"deferred-fetch wall time (see {json_path})")
    if report["_summary"]["gate_contact_speedup"] is False:
        raise AssertionError(
            f"contact-plan speedup gate: {contact['speedup']:.2f}x < "
            f"{CONTACT_SPEEDUP_GATE}x at {contact['n_sats']} sats x "
            f"{contact['stations']} stations (see {json_path})")
    if report["_summary"]["gate_async_hidden"] is False:
        raise AssertionError(
            f"async overlap gate: hidden fraction "
            f"{contact['async_recount_hidden_frac']:.2f} < "
            f"{ASYNC_HIDE_GATE} of recount wall time (see {json_path})")
    if depth and depth["pred_max_dev"] > CONTACT_PARITY_TOL:
        raise AssertionError(
            f"depth-sweep parity gate: pred_max_dev="
            f"{depth['pred_max_dev']:.3e} exceeds {CONTACT_PARITY_TOL} "
            f"across pipeline depths {depth['depths']} (see {json_path})")
    if report["_summary"]["gate_depth2_hidden_ge_depth1"] is False:
        raise AssertionError(
            f"depth-scaling gate: depth-2 hidden fraction "
            f"{depth['per_depth']['2']['hidden_frac']:.2f} < depth-1's "
            f"{depth['per_depth']['1']['hidden_frac']:.2f} "
            f"(see {json_path})")
    if faults:
        if faults["watchdog_pred_max_dev"] > 0.0:
            raise AssertionError(
                f"watchdog parity gate: async crash-recovery arm deviates "
                f"{faults['watchdog_pred_max_dev']:.3e} from the "
                f"synchronous arm (see {json_path})")
        if not faults["retry_recovers_all_rates"]:
            raise AssertionError(
                f"retry gate: the bounded-retry arm delivered fewer "
                f"ground-kept bytes than the no-retry arm at some fault "
                f"rate (see {json_path})")
        if report["_summary"]["gate_fault_overhead"] is False:
            raise AssertionError(
                f"fault-subsystem overhead gate: FaultPlan.none() costs "
                f"{faults['none_plan_overhead']:+.1%} vs faults=None "
                f"(>= {FAULT_OVERHEAD_GATE:.0%}, see {json_path})")
    return rows


if __name__ == "__main__":
    if "--devices" in sys.argv:  # e.g. --devices 1,2,4
        os.environ["FLEET_BENCH_DEVICES"] = \
            sys.argv[sys.argv.index("--devices") + 1]
    if "--stations" in sys.argv:  # e.g. --stations 8
        os.environ["FLEET_BENCH_STATIONS"] = \
            sys.argv[sys.argv.index("--stations") + 1]
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
