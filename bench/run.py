#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell (an entry of ``workloads`` in
``BENCHMARK.json``) names a configuration and a traffic mix; the files
they name are found by name (``benchlib/loader.py``). The run sets up
(weights and scenes from ``--seed``, one warm-up cycle of the traffic's
pass pool, which compiles or loads every program), then drives the
entry in a closed loop for ``--seconds``; with ``--trace 1`` it instead
traces two cycles of the pool and reduces the trace to the cell's
per-layer metrics. Afterwards every session the window ran is compared
with the plain reference (``benchlib/check.py``). The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced),
then ``checks``, each compared number beside its limit.

It exits non-zero and prints no result when JAX finds no TPU or fewer
chips than the cell asks for; it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

T_IMPORT = time.time()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def process_start() -> float:
    """Wall-clock time at which this process started (Linux), else the
    time this module was imported."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        return min(btime + ticks / os.sysconf("SC_CLK_TCK"), T_IMPORT)
    except (OSError, ValueError, IndexError, StopIteration):
        return T_IMPORT


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Ctx:
    """What a driver is given: the cell's files and its seeds."""

    def __init__(self, cell, config, traffic, seed: int, chips: int):
        import numpy as np
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.chips = seed, chips
        ss = np.random.SeedSequence([seed % 2 ** 64, seed // 2 ** 64])
        w, s = ss.spawn(2)
        self.weights_seed = int(w.generate_state(1)[0] >> 1)
        self.scene_seed = s

    @property
    def weights_key(self):
        import jax
        return jax.random.PRNGKey(self.weights_seed)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (the smallest value with at least q% of
    the values at or below it)."""
    v = sorted(values)
    return v[max(math.ceil(q / 100.0 * len(v)) - 1, 0)]


def window(driver, seconds: float):
    """Closed loop for ``seconds``: -> (latencies s, tiles, seconds)."""
    lat, tiles, r = [], 0, 0
    t_start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        tiles += driver.round(r)
        lat.append(time.perf_counter() - t0)
        driver.after_round(r)
        r += 1
        if time.perf_counter() - t_start >= seconds:
            break
    driver.close()
    return lat, tiles, time.perf_counter() - t_start


def traced_window(driver, n_rounds: int, trace_dir: str):
    """``n_rounds`` rounds under the profiler, with the benchmark's host
    spans (``trace.SPANS``) and a clock mark before and after. The
    profiler's host and Python tracers are off: they slow the host many
    times over. -> (round latencies s, spans, marks)."""
    import jax
    from benchlib import trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    lat = []
    trace.SPANS.events, trace.SPANS.on = [], True
    try:
        with jax.profiler.trace(trace_dir, profiler_options=opts):
            marks = [trace.clock_mark()]
            with trace.SPANS("bench.window"):
                for r in range(n_rounds):
                    t0 = time.perf_counter()
                    with trace.SPANS("bench.round"):
                        driver.round(r)
                    lat.append(time.perf_counter() - t0)
                    driver.after_round(r)
                driver.close()
            marks.append(trace.clock_mark())
    finally:
        trace.SPANS.on = False
    return lat, list(trace.SPANS.events), marks


def execute(bench, cell, config, traffic, limits, drv_mod, metric_mods,
            seed: int, seconds: float, trace_on: int, devs, peaks,
            t_proc: float, ref_mode: str = "default") -> dict:
    """Set up, warm up, run the window (or the traced window), compare
    with the reference; -> the result object. ``devs``: the devices the
    cell runs on; ``ref_mode``: the precision the configuration states
    for the counters' convolutions on this platform."""
    from benchlib import check, loader
    from benchlib.meter import Meter
    kind = devs[0].device_kind
    with Meter() as meter:
        ctx = Ctx(cell, config, traffic, seed, cell["chips"])
        driver = drv_mod.Driver(ctx)
        driver.setup()
        driver.warmup()
        if trace_on:
            from benchlib import trace
            trace.clock_mark()  # the marker program, before the window
        setup_s = time.time() - t_proc
        log(f"setup: {setup_s:.3f} s, backend compiles {meter.compiles} "
            f"({meter.compile_s:.3f} s), cache hits {meter.hits}, "
            f"misses {meter.misses}")
        c0 = meter.compiles
        metrics, breakdown, dev_extra = {}, None, {}
        if trace_on:
            import shutil
            import tempfile
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            try:
                lat, spans, marks = traced_window(
                    driver, driver.trace_rounds(), tdir)
                log(f"traced window: {len(lat)} rounds; round median "
                    f"{1e3 * percentile(lat, 50):.3f} ms, max "
                    f"{1e3 * max(lat):.3f} ms")
                devices = trace.load(tdir, cell["chips"])
            finally:
                shutil.rmtree(tdir, ignore_errors=True)
            spans, skew = trace.align(spans, marks, devices)
            log(f"traced window: host spans put on the trace's clock, the "
                f"two clock marks agree to {skew / 1e3:.1f} us")
            summ = trace.reduce(devices, spans)
            run = dict(trace=summ, tally=driver.tally, config=config,
                       traffic=traffic, peaks=peaks, chips=cell["chips"],
                       flops=driver.flops())
            for name, mod in metric_mods.items():
                v = mod.read(run)
                if v is not None:
                    unit = next(m["unit"] for m in bench["per_layer"]
                                if m["name"] == name)
                    metrics[name] = {"value": v, "unit": unit}
            dev_extra = {"busy_s": summ.busy_s, "window_s": summ.window_s}
            breakdown = {"device_ops": summ.top_ops(),
                         "idle_gaps": summ.top_idle()}
            attempted = driver.tally["rounds"]
        else:
            lat, tiles, secs = window(driver, seconds)
            attempted = len(lat)
            e2e = {m["name"]: m for m in loader.end_to_end_for(bench,
                                                               cell["name"])}
            values = {"tiles_per_s": tiles / secs,
                      "round_p95_ms": 1e3 * percentile(lat, 95),
                      "setup_s": setup_s}
            metrics = {k: {"value": values[k], "unit": e2e[k]["unit"]}
                       for k in e2e}
            log(f"window: {attempted} rounds, {tiles} tiles in {secs:.3f} s; "
                f"round median {1e3 * percentile(lat, 50):.3f} ms, p95 "
                f"{1e3 * percentile(lat, 95):.3f} ms, max "
                f"{1e3 * max(lat):.3f} ms")
        log(f"window: backend compiles {meter.compiles - c0}")
    stats = [d.memory_stats() or {} for d in devs]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    sessions, passes = driver.outputs()
    driver.drop_program_state()
    from benchlib.reference import Reference
    t_ref = time.perf_counter()
    ref = Reference(driver.counters, config, traffic, mode=ref_mode)
    numbers = check.compare(sessions, ref, passes)
    correct, rows = check.verdict(numbers, limits)
    log(f"reference: {len(sessions)} sessions compared in "
        f"{time.perf_counter() - t_ref:.3f} s")
    failed = 0 if correct else attempted
    device = {"platform": devs[0].platform, "kind": kind,
              "count": cell["chips"], "memory_peak_bytes": peak, **dev_extra}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    for k, v, lim in rows:
        log(f"check {k}: {v!r} limit {lim!r} "
            f"{'ok' if v <= lim else 'FAIL'}")
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    t_proc = process_start()
    args = parse(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from benchlib import loader
    try:
        bench = loader.benchmark(ROOT)
        cell = loader.workload(bench, args.workload)
        config = loader.config(cell["config"])
        traffic = loader.traffic(cell["traffic"])
        limits = loader.limits(cell["name"])
        drv_mod = loader.driver(traffic["entry"])
        metric_mods = {m["name"]: loader.metric(m["name"])
                       for m in loader.per_layer_for(bench, cell["name"])}
    except (LookupError, OSError, ValueError) as e:
        log(f"run: {e}")
        return 2
    # the benchmark's compile cache, inside the checkout at a fixed path;
    # the program's compile_cache.enable() takes it from here
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    try:
        import jax
        from repro.launch import compile_cache
    except ImportError as e:
        log(f"run: the program is not in this checkout ({e})")
        return 2
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"run: needs a TPU, JAX found {devs[0].platform!r}")
        return 1
    if len(devs) < cell["chips"]:
        log(f"run: the cell needs {cell['chips']} chips, {len(devs)} visible")
        return 1
    kind = devs[0].device_kind
    compile_cache.enable()
    out = execute(bench, cell, config, traffic, limits, drv_mod, metric_mods,
                  args.seed, args.seconds, args.trace, devs[:cell["chips"]],
                  loader.peaks(kind), t_proc)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
