"""Collaborative serving driver: batched tile requests through the
TargetFuse cascade (the paper-kind end-to-end path).

  PYTHONPATH=src python -m repro.launch.serve --frames 4 --revisits 3

Trains (or loads cached) reduced counters, then runs a one-window
Mission for every registered selection policy and prints the CMAE
table.
"""
from __future__ import annotations

import argparse
import os

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.core.cascade import fit_counter
from repro.core.mission import Mission
from repro.core.pipeline import PipelineConfig
from repro.core.policies import available_policies
from repro.data.synthetic import DATASETS, SceneSpec, make_scene, revisit_frames
from repro.launch import compile_cache

CACHE = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                     "artifacts", "counters")


def get_counters(train_steps=(800, 2000), scene=None, force=False,
                 cache_dir=CACHE, seed=0):
    """(space (params, cfg), ground (params, cfg)) — cached on disk.

    Trained on a MIX of scene profiles (mini + the three dataset
    analogues) so confidence calibration transfers across benchmarks.
    """
    from repro.checkpoint import ckpt

    sp_cfg = reduced(get_config("targetfuse-space"))
    gd_cfg = reduced(get_config("targetfuse-ground"))
    pair = []
    rng = np.random.default_rng(seed)
    if scene is not None:
        profiles = [scene]
    else:
        from repro.data.synthetic import SceneSpec as SS
        profiles = [
            SceneSpec("mini", 512, (20, 30), (10, 24), cloud_fraction=0.2),
            SS("xview", 768, (30, 60), (8, 20), cloud_fraction=0.3),
            SS("dota", 768, (22, 45), (10, 32), cloud_fraction=0.3),
            SS("uavod", 512, (8, 24), (12, 40), cloud_fraction=0.2),
        ]
    scenes = []
    for p in profiles:
        scenes += [make_scene(rng, p) for _ in range(max(2, 8 // len(profiles)))]
    for name, cfg, steps, k in (("space", sp_cfg, train_steps[0], 0),
                                ("ground", gd_cfg, train_steps[1], 1)):
        d = os.path.join(cache_dir, name)
        from repro.models import detector
        template = detector.init(jax.random.PRNGKey(k), cfg)
        if not force:
            try:
                _, params = ckpt.restore(d, template)
                pair.append((params, cfg))
                continue
            except (FileNotFoundError, ValueError):
                pass
        params, loss = fit_counter(cfg, scenes, 128, steps, jax.random.PRNGKey(k))
        ckpt.save(d, steps, params)
        pair.append((params, cfg))
    return pair[0], pair[1]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=3)
    ap.add_argument("--revisits", type=int, default=3)
    ap.add_argument("--dataset", default="mini")
    ap.add_argument("--bandwidth", type=float, default=50.0)
    ap.add_argument("--retrain", action="store_true")
    args = ap.parse_args()
    compile_cache.enable()

    spec = (DATASETS[args.dataset] if args.dataset in DATASETS
            else SceneSpec("mini", 512, (20, 30), (10, 24), cloud_fraction=0.2))
    space, ground = get_counters(force=args.retrain)

    rng = np.random.default_rng(1)
    frames = []
    for _ in range(args.frames):
        img, b, c = make_scene(rng, spec)
        frames += revisit_frames(rng, img, b, c, args.revisits)
    print(f"{len(frames)} frames, {(spec.scene_px // 128) ** 2} tiles each")

    print(f"{'method':14s} {'CMAE':>7s} {'pred':>6s} {'true':>6s} "
          f"{'down':>5s} {'proc':>5s} {'MB':>7s}")
    for method in available_policies():
        pcfg = PipelineConfig(method=method, bandwidth_mbps=args.bandwidth,
                              score_thresh=0.25)
        s = Mission(space, ground, pcfg).run(frames).summary()
        print(f"{method:14s} {s['cmae']:7.3f} {s['total_pred']:6.0f} "
              f"{s['total_true']:6.0f} {s['tiles_downlinked']:5d} "
              f"{s['tiles_processed_space']:5d} "
              f"{s['bytes_downlinked'] / 1e6:7.2f}")


if __name__ == "__main__":
    main()
