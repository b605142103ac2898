"""Quickstart: the TargetFuse pipeline on one synthetic EO scene via the
Mission API.

  PYTHONPATH=src python examples/quickstart.py

A Mission executes the paper's Fig. 3 workflow as an explicit stage
graph — ingest(frames) runs Capture -> RoiFilter -> Dedup ->
OnboardCount under the energy budget; contact_window() runs Select ->
Downlink -> GroundRecount -> Aggregate under the byte budget — with the
five baselines available as registered selection policies.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core.mission import Mission
from repro.core.pipeline import PipelineConfig
from repro.core.policies import available_policies
from repro.data.synthetic import SceneSpec, make_scene, revisit_frames
from repro.launch.serve import get_counters
from repro.launch import compile_cache


def main():
    print("== TargetFuse quickstart (Mission API) ==")
    spec = SceneSpec("demo", 512, (24, 32), (10, 24), cloud_fraction=0.2)
    rng = np.random.default_rng(42)
    img, boxes, classes = make_scene(rng, spec)
    frames = revisit_frames(rng, img, boxes, classes, 2)
    print(f"scene: {img.shape}, {len(boxes)} objects, "
          f"{(spec.scene_px // 128) ** 2} tiles x {len(frames)} revisits")
    print(f"registered selection policies: {', '.join(available_policies())}")

    space, ground = get_counters()

    # full system, streamed: onboard stages at ingest, ground stages at
    # the contact window
    mission = Mission(space, ground,
                      PipelineConfig(method="targetfuse", score_thresh=0.25))
    ing = mission.ingest(frames)
    print(f"ingest: {ing.n_tiles} tiles, {ing.tiles_processed_space} counted "
          f"onboard within {ing.energy_granted_j:.1f} J")
    win = mission.contact_window()
    print(f"contact window: {win.tiles_downlinked} tiles downlinked "
          f"({win.bytes_spent / 1e6:.2f} MB of {win.budget_bytes / 1e6:.2f} MB)")
    r = mission.result()
    print(f"counts: true={r.total_true:.0f} pred={r.total_pred:.0f} "
          f"CMAE={r.cmae:.3f}")

    # same frames through the space-only policy for comparison
    so = Mission(space, ground,
                 PipelineConfig(method="space_only",
                                score_thresh=0.25)).run(frames)
    print(f"vs space-only CMAE={so.cmae:.3f} "
          f"({so.cmae / max(r.cmae, 1e-9):.1f}x better)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
