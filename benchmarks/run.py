"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Select figures with
``python -m benchmarks.run fig7 fig11`` (all by default). Pass
``--json PATH`` to also write the rows as a ``name ->
{us_per_call, derived}`` dict (the ``BENCH_*.json`` trajectory files).
A module that raises is reported as an ERROR row and the harness keeps
going through the later modules, then exits nonzero: a bench-embedded
gate (e.g. the fleet/loop parity assert) always fails the run.
Compiled programs persist in the compile cache
(:mod:`repro.launch.compile_cache`).
"""
from __future__ import annotations

import json
import sys
import time

FIGS = ("fig4", "fig6", "fig7", "fig8", "fig9", "fig10", "fig11", "fig12",
        "pipeline", "fleet", "kernels", "orbits")


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    argv = sys.argv[1:]
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        try:
            json_path = argv[i + 1]
        except IndexError:
            sys.exit("--json requires a PATH argument")
        del argv[i:i + 2]
    want = [a for a in argv if not a.startswith("-")] or list(FIGS)
    mods = []
    if "fig4" in want:
        from benchmarks import fig4_tilesize as m
        mods.append(m)
    if "fig6" in want:
        from benchmarks import fig6_conf_policies as m
        mods.append(m)
    if "fig7" in want:
        from benchmarks import fig7_bandwidth as m
        mods.append(m)
    if "fig8" in want:
        from benchmarks import fig8_energy as m
        mods.append(m)
    if "fig9" in want:
        from benchmarks import fig9_hardware as m
        mods.append(m)
    if "fig10" in want:
        from benchmarks import fig10_counters as m
        mods.append(m)
    if "fig11" in want:
        from benchmarks import fig11_datasets as m
        mods.append(m)
    if "fig12" in want:
        from benchmarks import fig12_ablation as m
        mods.append(m)
    if "pipeline" in want:
        from benchmarks import pipeline_bench as m
        mods.append(m)
    if "fleet" in want:
        from benchmarks import fleet_bench as m
        mods.append(m)
    if "kernels" in want:
        from benchmarks import kernel_bench as m
        mods.append(m)
    if "orbits" in want:
        from benchmarks import orbits_bench as m
        mods.append(m)

    results = {}
    failed = []
    print("name,us_per_call,derived")
    for mod in mods:
        t0 = time.time()
        try:
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived}", flush=True)
                results[name] = {"us_per_call": us, "derived": derived}
        except Exception as e:  # keep the harness running for later figs
            print(f"{mod.__name__},0.0,ERROR={e!r}", flush=True)
            results[mod.__name__] = {"us_per_call": 0.0, "derived": f"ERROR={e!r}"}
            failed.append(mod.__name__)
        print(f"# {mod.__name__} done in {time.time() - t0:.0f}s",
              file=sys.stderr)

    if json_path:
        with open(json_path, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# wrote {json_path}", file=sys.stderr)
    if failed:
        sys.exit(f"benchmark module(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
