"""A benchmark cell cut to a size the CPU runs in seconds, for the
harness's tests: the yolo cell's files with 32-px counters of three
narrow stages, 128-px scenes and 32-px tiles. Nothing here is measured."""
from __future__ import annotations

import importlib.util
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import loader  # noqa: E402

CELL = "yolo.pass-targetfuse"
SEED = 2 ** 33 + 5  # seeds above 32 bits must work


def tiny_limits():
    """The tiny cell's limits: the yolo cell's, with the counters' two
    set for this size. Here the program and the reference both run in
    float32 and agree to 2e-8 in confidence with no count missed, while
    the int8 control misses 0.087 of the counts by 4.2e-3 in confidence
    (the cell's own limits are set from chip readings at its size)."""
    out = dict(loader.limits(CELL))
    out.update(count_miss=0.03, conf_gap=1e-3)
    return out


def run_module():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_cell():
    bench = loader.benchmark()
    cell = loader.workload(bench, CELL)
    config = loader.config(cell["config"])
    traffic = loader.traffic(cell["traffic"])
    for role in ("space", "ground"):
        config["counters"][role].update(input_size=32, widths=[4, 8, 16],
                                        n_blocks_per_stage=1)
    traffic["scenes"].update(scene_px=128, objects_per_scene=[4, 10],
                             object_px=[4, 10])
    traffic.update(tile_px=32, pool_passes=3, session_rounds=2)
    return bench, cell, config, traffic


def execute(seed=SEED, seconds=0.5, trace_on=0, limits=None):
    """Run the tiny cell through the harness on the CPU (the look for a
    chip skipped); -> the result object."""
    import jax
    bench, cell, config, traffic = tiny_cell()
    run = run_module()
    mets = {m["name"]: loader.metric(m["name"])
            for m in loader.per_layer_for(bench, cell["name"])}
    peaks = loader.peaks("TPU v5 lite")
    return run.execute(bench, cell, config, traffic,
                       limits or tiny_limits(),
                       loader.driver(traffic["entry"]), mets, seed, seconds,
                       trace_on, jax.devices()[:1], peaks, time.time(),
                       ref_mode="highest")
