#!/usr/bin/env python3
"""Trace one cell with the program's own spans and counters on, on the chip.

    python3 bench/spans.py --workload <cell> --seed <n> [--blocks 2]

A stopgap beside ``bench/run.py``: until its traced window turns the
program's recorder (``repro.core.obs``) on itself and hands the
program's spans and counters to the per-layer readers, this script
does so, and reads ``benchlib/program_spans.py``'s metrics. Once
``bench/run.py`` does, this script goes. Its readings are not compared
with the reference; a cell's ``correct`` comes from ``bench/run.py``.

Sets the cell up as a run does (weights and scenes from the seed, one
warm-up cycle of the pass pool). Then it runs the traced window of
``bench/run.py`` with the recorder on, puts the program's spans on the
trace's clock beside the benchmark's own, and reads:

* the cell's per-layer metrics, as a traced run reads them;
* the program-span metrics of ``benchlib/program_spans.py``;
* the counting programs' device time by the tier that dispatched them,
  against the count programs' whole device time;
* device-idle time by the innermost span open (program spans
  included), host time per round of each program span, and the share
  of the capture stage's idle time that program spans name;
* the compiles in the window, by the span that was open.

Last, in the same process, it measures what the recorder costs:
``--blocks`` pairs of blocks of as many rounds as the traced window,
one block with the recorder off and one with it on, the profiler off in
both.

The last line of standard output is one JSON object. It exits non-zero
when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def run_rounds(driver, n: int, record: bool):
    """``n`` untraced rounds, the recorder on or off -> latencies s."""
    from repro.core import obs
    lat = []
    if record:
        obs.enable()
    try:
        for r in range(n):
            t0 = time.perf_counter()
            driver.round(r)
            lat.append(time.perf_counter() - t0)
            driver.after_round(r)
        driver.close()
    finally:
        if record:
            obs.disable()
    return lat


def recorded_window(run_mod, driver, n: int, trace_dir: str):
    """``bench/run.py``'s traced window with the program's recorder on.
    -> (latencies s, benchmark spans, marks, program spans of this
    thread, the program's counter deltas, program spans recorded)."""
    from repro.core import obs
    c0 = obs.counters()
    obs.enable()
    try:
        lat, spans, marks = run_mod.traced_window(driver, n, trace_dir)
    finally:
        obs.disable()
    c1 = obs.counters()
    counters = {k: v - c0.get(k, 0) for k, v in c1.items()
                if v != c0.get(k, 0)}
    return lat, spans, marks, obs.events(), counters, len(obs.records())


def capture_idle(summ_bench, summ_prog) -> dict:
    """The capture stage's idle time as the benchmark's spans put it, and
    the part of it that the program's ``capture.*`` spans name."""
    stage = summ_bench.idle_by_span.get("bench.stage.capture", 0.0)
    named = sum(v for k, v in summ_prog.idle_by_span.items()
                if k.startswith("capture."))
    return {"stage_idle_s": stage / 1e9, "capture_spans_idle_s": named / 1e9,
            "share": named / stage if stage else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--blocks", type=int, default=2)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import importlib.util

    import jax
    from benchlib import loader, program_spans, trace
    from repro.launch import compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("spans: needs a TPU", file=sys.stderr)
        return 1
    compile_cache.enable()
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    run_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run_mod)
    bench = loader.benchmark(ROOT)
    cell = loader.workload(bench, args.workload)
    config = loader.config(cell["config"])
    traffic = loader.traffic(cell["traffic"])
    metric_mods = {m["name"]: loader.metric(m["name"])
                   for m in loader.per_layer_for(bench, cell["name"])}
    peaks = loader.peaks(devs[0].device_kind)
    log = run_mod.log

    ctx = run_mod.Ctx(cell, config, traffic, args.seed, cell["chips"])
    driver = loader.driver(traffic["entry"]).Driver(ctx)
    driver.setup()
    driver.warmup()
    trace.clock_mark()
    n = driver.trace_rounds()

    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        lat_tr, spans, marks, prog, counters, n_rec = recorded_window(
            run_mod, driver, n, tdir)
        devices = trace.load(tdir, cell["chips"])
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    bench_spans, _ = trace.align(spans, marks, devices)
    all_spans, skew = trace.align(spans + prog, marks, devices)
    summ_b = trace.reduce(devices, bench_spans)
    summ = trace.reduce(devices, all_spans)
    by_span = program_spans.module_ns_by_span(devices, summ.spans,
                                              summ.t0, summ.t1)
    tally = {k: v for k, v in driver.tally.items() if not isinstance(v, list)}
    run = dict(trace=summ, tally=driver.tally, config=config,
               traffic=traffic, peaks=peaks, chips=cell["chips"],
               flops=driver.flops(), counters=counters,
               module_ns_by_span=by_span, devices=devices)
    rounds = tally["rounds"]
    metrics = {}
    for name, mod in metric_mods.items():
        metrics[name] = mod.read(run)
    for name, (_, read) in program_spans.METRICS.items():
        metrics[name] = read(run)
    count_ms = 1e3 * summ.module_s(*program_spans.COUNT_PROGRAMS) / rounds
    tiers = {"space_ms_per_round":
             program_spans.count_space_device_ms_per_round(run),
             "ground_ms_per_round":
             program_spans.count_ground_device_ms_per_round(run),
             "count_programs_ms_per_round": count_ms}
    tiers["tiers_over_all"] = ((tiers["space_ms_per_round"] or 0.0)
                               + (tiers["ground_ms_per_round"] or 0.0)
                               ) / count_ms if count_ms else None
    host = program_spans.host_ms_by_span(
        [sp for sp in summ.spans if not sp[0].startswith("bench.")],
        summ.t0, summ.t1)

    from repro.core import obs
    med = statistics.median
    blocks = {False: [], True: []}
    spans_on = 0
    for b in range(args.blocks):
        # off, on, on, off, ...: a drift over the process falls on both
        for record in ((False, True) if b % 2 == 0 else (True, False)):
            blocks[record].append(run_rounds(driver, n, record=record))
            spans_on += len(obs.records()) if record else 0
    lat_off = [x for blk in blocks[False] for x in blk]
    lat_on = [x for blk in blocks[True] for x in blk]

    out = {
        "workload": cell["name"], "seed": args.seed, "rounds": rounds,
        "device": {"kind": devs[0].device_kind, "count": cell["chips"],
                   "busy_s": summ.busy_s, "window_s": summ.window_s},
        "metrics": metrics,
        "overhead": {"rounds_each": len(lat_off),
                     "off_median_ms": 1e3 * med(lat_off),
                     "on_median_ms": 1e3 * med(lat_on),
                     "traced_median_ms": 1e3 * med(lat_tr),
                     "off_block_medians_ms": [1e3 * med(b)
                                              for b in blocks[False]],
                     "on_block_medians_ms": [1e3 * med(b)
                                             for b in blocks[True]],
                     "spans_per_round": spans_on / len(lat_on),
                     "traced_spans_per_round": n_rec / rounds},
        "count_by_tier": tiers,
        "tally": tally,
        "capture_idle": capture_idle(summ_b, summ),
        "idle_gaps": summ.top_idle(16),
        "host_ms_per_round": {k: v / rounds for k, v in sorted(
            host.items(), key=lambda kv: -kv[1])},
        "device_ms_per_round_by_span": {
            f"{sp} {mod}": v / 1e6 / rounds for (sp, mod), v in sorted(
                ((k, v) for k, v in by_span.items()
                 if not k[0].startswith("bench.")),
                key=lambda kv: -kv[1])[:40]},
        "counters": counters,
        "clock_skew_us": skew / 1e3,
    }
    compiles = {k: v for k, v in counters.items() if k.startswith("compile@")}
    log(f"spans: compiles in the traced window, by open span: {compiles}")
    log(f"spans: recorder off {out['overhead']['off_median_ms']:.3f} ms, on "
        f"{out['overhead']['on_median_ms']:.3f} ms, traced "
        f"{out['overhead']['traced_median_ms']:.3f} ms (round medians)")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
