#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3]

For each of ``--seeds`` it sets the cell up as a run does (weights and
scenes from the seed, one warm-up cycle of the pass pool), drives the
program through as many rounds as a traced run does, exactly as the
window drives them, and compares its sessions with the reference: the
lower readings. For each of ``--control-seeds`` it puts each control in
the program's place (the reference one precision step below what the
configuration states: ``control`` in every stage, ``control-capture``
with capture and dedup features in bfloat16, ``control-count`` with the
counters' convs in int8) and compares that: the upper readings. One
JSON line per reading on standard output, then the worst lower reading
of each number and the least upper reading of each number under each
control.

A sound seed's line also gives ``own_rep_differ``, not compared: the
worst share of a round's tiles whose ROI verdict or representative
differs when the reference's ROI filter and dedup run on the
reference's own moments instead of the program's. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def own_rep_differ(sessions, ref, passes) -> float:
    """Worst share of a round's tiles whose ROI verdict or
    representative differs between the program and the reference's ROI
    filter and dedup on the reference's own moments (a member tied for
    its cluster's representative may stand for it)."""
    worst = 0.0
    for s in sessions:
        for key, pr in zip(s["keys"], s["rounds"]):
            _, mom_ref = ref.capture(key, passes[key])
            active = ref.active(mom_ref)
            rep_of = ref.rep_of(mom_ref, active, follow=pr["rep_of"])
            worst = max(worst, float(((pr["active"] != active)
                                      | (pr["rep_of"] != rep_of)).mean()))
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import importlib.util

    import jax
    from benchlib import check, loader
    from benchlib.reference import CONTROLS, Reference
    from repro.launch import compile_cache

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    compile_cache.enable()
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    bench = loader.benchmark(ROOT)
    cell = loader.workload(bench, args.workload)
    config = loader.config(cell["config"])
    traffic = loader.traffic(cell["traffic"])
    drv_mod = loader.driver(traffic["entry"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctrl = [int(s) for s in args.control_seeds.split(",") if s]
    lower, upper = {}, {}
    for seed in sorted(set(seeds) | set(ctrl)):
        t0 = time.perf_counter()
        driver = drv_mod.Driver(run.Ctx(cell, config, traffic, seed,
                                        cell["chips"]))
        driver.setup()
        passes = dict(enumerate(driver.pool))
        ref = Reference(driver.counters, config, traffic, mode="default")
        if seed in seeds:
            driver.warmup()
            for r in range(driver.trace_rounds()):
                driver.round(r)
                driver.after_round(r)
            driver.close()
            sessions, _ = driver.outputs()
            driver.drop_program_state()
            nums = check.compare(sessions, ref, passes)
            print(json.dumps({"seed": seed, "side": "program", **nums,
                              "own_rep_differ": own_rep_differ(
                                  sessions, ref, passes)}), flush=True)
            for k, v in nums.items():
                lower[k] = max(lower.get(k, 0.0), v)
        if seed in ctrl:
            keys = list(passes)
            for mode in CONTROLS:
                c = Reference(driver.counters, config, traffic, mode=mode)
                rounds, summary = c.session([passes[k] for k in keys], keys)
                nums = check.compare([dict(keys=keys, rounds=rounds,
                                           summary=summary)], ref, passes)
                print(json.dumps({"seed": seed, "side": mode, **nums}),
                      flush=True)
                up = upper.setdefault(mode, {})
                for k, v in nums.items():
                    up[k] = min(up.get(k, float("inf")), v)
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
    print(json.dumps({"workload": cell["name"], "lower": lower,
                      "upper": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
