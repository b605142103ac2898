"""End-to-end pipeline throughput: device-resident engine vs the seed
host-orchestrated reference path.

Two fig11-style (dataset-analogue, unlimited-downlink) workloads:

* **method sweep** — one standard frame set per dataset x all five
  baseline methods: per-method frames/sec + tiles/sec and the parity
  gate (per-tile predictions bit-identical-or-within-1e-5). Both arms
  run INTERLEAVED in ONE subprocess, each cell warmed once and then
  timed best-of-2 — steady-state throughput. (Cold-cache isolation is
  pointless here, and sequential whole arms measured minutes apart pick
  up >2x machine-speed drift on throttled CI boxes, which used to swamp
  the per-cell signal.)
* **pass sequence** — successive targetfuse runs over frame sets of
  VARYING size per dataset, like successive orbital passes. This is the
  headline number and is deliberately timed cold, single-shot, each arm
  starting from cleared in-memory compile caches with the persistent
  compile cache off, so neither inherits the other's compiled programs
  (all arms run in one process: one process per chip). Every pass
  presents new array shapes, so the seed path
  recompiles its counting/ROI programs per pass while the engine's
  fixed-shape programs (frame buckets, size-tiered count batches) are
  compiled once, ever — the per-distinct-shape recompiles are exactly
  the cost the engine removes.

Writes ``BENCH_pipeline.json``.
"""
from __future__ import annotations

import json
import os

METHODS = ("space_only", "ground_only", "tiansuan", "kodan", "targetfuse")
UNLIMITED = dict(bandwidth_mbps=100000.0, contact_s=3600.0)
# (n_scenes, revisits) per orbital pass. Frame counts are distinct within
# each dataset AND across the two same-resolution datasets (xview/dota are
# both 768 px), so no two reference-path runs can share compiled programs
# — each pass presents genuinely new shapes, as successive real passes do.
PASSES = {
    "xview": ((1, 2), (2, 4), (1, 5), (2, 2), (1, 3)),
    "dota": ((1, 7), (3, 3), (2, 5), (2, 6), (1, 13)),
    "uavod": ((1, 2), (2, 4), (1, 5), (2, 2), (1, 3)),
}
JSON_PATH = "BENCH_pipeline.json"


def _arm(arm: str) -> dict:
    """``sweep``: both arms interleaved, steady-state. ``ref`` /
    ``engine``: that arm's cold pass sequence."""
    import time

    import numpy as np

    from benchmarks.common import BENCH_DATASETS, counters, frames_for
    from repro.core.mission import Mission
    from repro.core.pipeline import PipelineConfig

    space, ground = counters()

    if arm == "sweep":
        out = {"ref": {}, "engine": {}}
        for name, spec in BENCH_DATASETS.items():
            frames = frames_for(spec)
            for m in METHODS:
                cell = {}
                for use_engine in (False, True):
                    pcfg = PipelineConfig(method=m, score_thresh=0.25,
                                          use_engine=use_engine, **UNLIMITED)
                    Mission(space, ground, pcfg).run(frames)  # compile warm
                    cell[use_engine] = [pcfg, None, None]  # dt, result
                for _ in range(2):  # interleaved best-of-2 per arm
                    for use_engine in (False, True):
                        pcfg, dt, _ = cell[use_engine]
                        t0 = time.perf_counter()
                        r = Mission(space, ground, pcfg).run(frames)
                        dt1 = time.perf_counter() - t0
                        cell[use_engine] = [
                            pcfg, dt1 if dt is None else min(dt, dt1), r]
                for use_engine, key in ((False, "ref"), (True, "engine")):
                    _, dt, r = cell[use_engine]
                    out[key][f"{name}_{m}"] = {
                        "s": dt,
                        "frames_per_s": len(frames) / dt,
                        "tiles_per_s": r.tiles_total / dt,
                        "cmae": r.cmae,
                        "pred": np.asarray(r.per_tile_pred).tolist(),
                    }
        return out

    use_engine = arm == "engine"
    out = {"passes": {}}
    for name, spec in BENCH_DATASETS.items():
        for i, (ns, rv) in enumerate(PASSES[name]):
            frames = frames_for(spec, n_scenes=ns, revisits=rv, seed=10 + i)
            pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25,
                                  use_engine=use_engine, **UNLIMITED)
            t0 = time.perf_counter()
            r = Mission(space, ground, pcfg).run(frames)
            dt = time.perf_counter() - t0
            out["passes"][f"{name}_pass{i}"] = {
                "s": dt,
                "tiles": r.tiles_total,
                "frames_per_s": len(frames) / dt,
                "tiles_per_s": r.tiles_total / dt,
                "pred": np.asarray(r.per_tile_pred).tolist(),
            }
    return out


def _cold(arm: str) -> dict:
    """One cold pass-sequence arm: nothing compiled before it counts."""
    import jax

    from repro.launch import compile_cache
    jax.clear_caches()
    with compile_cache.disabled():
        return _arm(arm)


def run(json_path: str = JSON_PATH):
    import numpy as np

    sweep = _arm("sweep")
    ref = _cold("ref")
    eng = _cold("engine")

    rows, report, max_dev = [], {"sweep": {}, "passes": {}}, 0.0

    def dev_of(r, e):
        return float(np.max(np.abs(np.asarray(r["pred"])
                                   - np.asarray(e["pred"])))) if r["pred"] else 0.0

    for k, r in sweep["ref"].items():
        e = sweep["engine"][k]
        dev = dev_of(r, e)
        max_dev = max(max_dev, dev)
        report["sweep"][k] = {
            "ref_s": r["s"], "engine_s": e["s"], "speedup": r["s"] / e["s"],
            "engine_frames_per_s": e["frames_per_s"],
            "engine_tiles_per_s": e["tiles_per_s"],
            "cmae": e["cmae"], "pred_max_dev": dev,
        }
        rows.append((f"pipeline_{k}", e["s"] * 1e6,
                     f"fps={e['frames_per_s']:.2f} tps={e['tiles_per_s']:.0f} "
                     f"speedup={r['s'] / e['s']:.2f}x dev={dev:.1e}"))

    ref_pass = eng_pass = 0.0
    for k, r in ref["passes"].items():
        e = eng["passes"][k]
        dev = dev_of(r, e)
        max_dev = max(max_dev, dev)
        ref_pass += r["s"]
        eng_pass += e["s"]
        report["passes"][k] = {
            "ref_s": r["s"], "engine_s": e["s"], "speedup": r["s"] / e["s"],
            "tiles": r["tiles"], "engine_tiles_per_s": e["tiles_per_s"],
            "pred_max_dev": dev,
        }
        rows.append((f"pipeline_{k}", e["s"] * 1e6,
                     f"tiles={r['tiles']} tps={e['tiles_per_s']:.0f} "
                     f"speedup={r['s'] / e['s']:.2f}x dev={dev:.1e}"))

    headline = ref_pass / eng_pass
    # machine provenance, mirroring fleet_bench: speedups measured on a
    # sub-2-core box are structure, not throughput — record why any
    # ratio gate downstream treats them as unenforceable
    from benchmarks.fleet_bench import _perf_gates_enforced
    report["_summary"] = {
        "cpu_cores": os.cpu_count(),
        "perf_gates_enforced": _perf_gates_enforced(),
        "targetfuse_pass_sequence_speedup": headline,
        "ref_pass_total_s": ref_pass, "engine_pass_total_s": eng_pass,
        "max_pred_dev": max_dev,
    }
    rows.append(("pipeline_targetfuse_speedup", eng_pass * 1e6,
                 f"{headline:.2f}x (ref {ref_pass:.1f}s -> engine "
                 f"{eng_pass:.1f}s) max_pred_dev={max_dev:.1e}"))
    with open(json_path, "w") as f:
        json.dump(report, f, indent=2)
    return rows


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
