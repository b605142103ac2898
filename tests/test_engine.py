"""Parity + equivalence tests for the device-resident pipeline engine.

The engine path (fused frame program, moments reuse, incremental
k-means++ init, fixed-shape counting batches) must reproduce the seed
host-orchestrated path prediction-for-prediction.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import dedup as dd
from repro.core import engine, tiling
from repro.core.cascade import (build_target_pool, count_tiles_batched,
                                count_tiles_batched_ref)
from repro.core.pipeline import PipelineConfig, run_pipeline
from repro.data.synthetic import (SceneSpec, boxes_to_targets,
                                  clip_boxes_to_tile, make_scene,
                                  revisit_frames)

SPEC = SceneSpec("mini", 384, (12, 18), (10, 24), cloud_fraction=0.2)
METHODS = ("space_only", "ground_only", "tiansuan", "kodan", "targetfuse")


# `counters` comes from tests/conftest.py (session-scoped, identical
# recipe — one training serves the engine/mission/fleet/golden suites)


@pytest.fixture(scope="module")
def frames():
    rng = np.random.default_rng(7)
    img, b, c = make_scene(rng, SPEC)
    return revisit_frames(rng, img, b, c, 3)


# ---------------------------------------------------------------------------
# end-to-end parity: engine vs pre-refactor reference path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", METHODS)
def test_engine_matches_reference_path(method, frames, counters):
    space, ground = counters
    res = {}
    for use_engine in (False, True):
        pcfg = PipelineConfig(method=method, score_thresh=0.25,
                              use_engine=use_engine)
        res[use_engine] = run_pipeline(frames, space, ground, pcfg)
    np.testing.assert_allclose(res[True].per_tile_pred,
                               res[False].per_tile_pred, atol=1e-5)
    assert abs(res[True].cmae - res[False].cmae) < 1e-5
    assert res[True].tiles_total == res[False].tiles_total
    assert res[True].tiles_processed_space == res[False].tiles_processed_space
    assert res[True].tiles_downlinked == res[False].tiles_downlinked


def test_prepared_frames_match_per_frame_tiling(frames):
    """Fused tile+resize+moments program == the seed per-frame host loop."""
    prep = engine.prepare_frames(frames, 128, 64, 48)
    sp, gd = [], []
    for img, _, _ in frames:
        t = tiling.tile_image(jnp.asarray(img), 128)
        sp.append(np.asarray(tiling.resize_tiles(t, 64)))
        gd.append(np.asarray(tiling.resize_tiles(t, 48)))
    sp, gd = np.concatenate(sp), np.concatenate(gd)
    assert prep.n == sp.shape[0]
    np.testing.assert_allclose(np.asarray(prep.tiles_sp)[:prep.n], sp,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(prep.tiles_gd)[:prep.n], gd,
                               atol=1e-6)
    # device arrays are padded to a power-of-two bucket with zero tiles
    assert prep.tiles_sp.shape[0] == dd.bucket_size(prep.n)
    assert float(jnp.abs(prep.tiles_sp[prep.n:]).sum()) == 0.0
    # ROI statistic from the moments == the seed's ad-hoc jnp.std pass
    raw_sd = np.asarray(jnp.mean(jnp.std(jnp.asarray(sp), axis=(1, 2)),
                                 axis=-1))
    np.testing.assert_allclose(prep.roi_std, raw_sd, atol=1e-5)


def test_prepared_frames_groups_mixed_resolutions():
    """Frames of different sizes are bucketed per shape, order preserved."""
    rng = np.random.default_rng(3)
    small = SceneSpec("s", 256, (4, 8), (10, 24), cloud_fraction=0.0)
    frames = []
    for spec in (SPEC, small, SPEC):
        img, b, c = make_scene(rng, spec)
        frames += revisit_frames(rng, img, b, c, 1)
    prep = engine.prepare_frames(frames, 128, 64, 48)
    expect, true = [], []
    from repro.data.synthetic import tile_counts
    for img, b, _ in frames:
        t = tiling.tile_image(jnp.asarray(img), 128)
        expect.append(np.asarray(tiling.resize_tiles(t, 64)))
        true.append(tile_counts(b, img.shape[0], 128))
    np.testing.assert_allclose(np.asarray(prep.tiles_sp)[:prep.n],
                               np.concatenate(expect), atol=1e-6)
    np.testing.assert_array_equal(prep.true, np.concatenate(true))


def _revisits(spec, n, seed):
    rng = np.random.default_rng(seed)
    img, b, c = make_scene(rng, spec)
    return revisit_frames(rng, img, b, c, n)


def _on_stacked_host_batches(frames, bucket=engine.FRAME_BUCKET):
    """The fused program's body run per resolution on explicitly stacked,
    zero-padded host batches of ``bucket`` frames; each frame's rows, in
    input order."""
    prog = jax.jit(engine._tile_batch, static_argnums=(1, 2, 3))
    groups = {}
    for i, (img, _, _) in enumerate(frames):
        groups.setdefault(img.shape, []).append(i)
    pieces = [None] * len(frames)
    for shape, idxs in groups.items():
        arr = np.zeros((-(-len(idxs) // bucket) * bucket, *shape), np.float32)
        for j, i in enumerate(idxs):
            arr[j] = frames[i][0]
        for c0 in range(0, len(arr), bucket):
            outs = [np.asarray(o) for o in
                    prog(jnp.asarray(arr[c0:c0 + bucket]), 128, 64, 48)]
            per = outs[0].shape[0] // bucket
            for j, i in enumerate(idxs[c0:c0 + bucket]):
                pieces[i] = [o[j * per:(j + 1) * per] for o in outs]
    return [np.concatenate([p[k] for p in pieces]) for k in range(4)]


_SMALL = SceneSpec("s", 256, (4, 8), (10, 24), cloud_fraction=0.0)
_WORKLOADS = {
    "3 frames": lambda: _revisits(SPEC, 3, 7),
    "4 frames": lambda: _revisits(SPEC, 4, 8),
    "8 frames": lambda: _revisits(SPEC, 8, 9),
    "mixed resolutions": lambda: (_revisits(SPEC, 2, 10)
                                  + _revisits(_SMALL, 1, 11)
                                  + _revisits(SPEC, 1, 12)),
}


@pytest.mark.parametrize("workload", list(_WORKLOADS))
def test_prepared_frames_bit_equal_to_stacked_host_batches(workload):
    """Frames placed on the device one by one (pad frames made there) give
    exactly the fused program's output on a stacked, zero-padded host
    batch; rows past ``n`` are exactly zero."""
    frames = _WORKLOADS[workload]()
    prep = engine.prepare_frames(frames, 128, 64, 48)
    sp, gd, mom, rs = _on_stacked_host_batches(frames)
    assert prep.n == sp.shape[0]
    for got, want in ((prep.tiles_sp, sp), (prep.tiles_gd, gd),
                      (prep.moments, mom)):
        np.testing.assert_array_equal(np.asarray(got)[:prep.n], want)
    np.testing.assert_array_equal(prep.roi_std, rs)
    for tiles in (prep.tiles_sp, prep.tiles_gd):
        assert not np.asarray(tiles)[prep.n:].any()


@pytest.mark.parametrize("workload", ["3 frames", "mixed resolutions"])
def test_device_frames_prepare_like_host_frames(workload):
    frames = _WORKLOADS[workload]()
    host = engine.prepare_frames(frames, 128, 64, 48)
    dev = engine.prepare_frames([(jnp.asarray(img), b, c)
                                 for img, b, c in frames], 128, 64, 48)
    assert dev.n == host.n
    for k in ("tiles_sp", "tiles_gd", "moments", "roi_std", "true"):
        np.testing.assert_array_equal(np.asarray(getattr(dev, k)),
                                      np.asarray(getattr(host, k)))


# ---------------------------------------------------------------------------
# component equivalence
# ---------------------------------------------------------------------------

def test_incremental_kmeanspp_matches_scan_init():
    """O(N·D)-per-pick init picks the same centroids as the seed's
    O(N·K·D) full-rescore scan."""
    x = jax.random.normal(jax.random.PRNGKey(3), (200, 9))
    for k in (2, 5, 16, 40):
        a = np.asarray(dd._kmeanspp_init(x, k, jax.random.PRNGKey(1)))
        b = np.asarray(dd._kmeanspp_init_scan(x, k, jax.random.PRNGKey(1)))
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_dedup_from_moments_matches_dedup(frames):
    from repro.kernels import ops as kops
    tiles = jnp.concatenate([tiling.resize_tiles(
        tiling.tile_image(jnp.asarray(f[0]), 128), 64) for f in frames])
    key = jax.random.PRNGKey(0)
    a = dd.dedup(tiles, 5, key)
    b = dd.dedup_from_moments(kops.tile_moments(tiles), 5, key)
    np.testing.assert_array_equal(np.asarray(a.assign), np.asarray(b.assign))
    np.testing.assert_array_equal(np.asarray(a.rep_idx), np.asarray(b.rep_idx))


def test_fixed_shape_count_batching_matches_reference(counters):
    (sp, sp_cfg), _ = counters
    rng = np.random.default_rng(2)
    for n in (1, 5, 70):
        tiles = rng.random((n, sp_cfg.input_size, sp_cfg.input_size, 3)
                           ).astype(np.float32)
        c0, f0 = count_tiles_batched_ref(sp, sp_cfg, tiles, score_thresh=0.25)
        c1, f1 = count_tiles_batched(sp, sp_cfg, tiles, score_thresh=0.25)
        np.testing.assert_allclose(c1, c0, atol=1e-5)
        np.testing.assert_allclose(f1, f0, atol=1e-5)


def test_count_batching_empty_input(counters):
    (sp, sp_cfg), _ = counters
    tiles = np.zeros((0, sp_cfg.input_size, sp_cfg.input_size, 3), np.float32)
    c, f = count_tiles_batched(sp, sp_cfg, tiles)
    assert c.shape == (0,) and f.shape == (0,)


def test_vectorized_target_pool_matches_loop():
    """build_target_pool == the seed's nested (ty, tx) Python loops."""
    from repro.models import detector
    cfg = reduced(get_config("targetfuse-space"))
    rng = np.random.default_rng(5)
    scenes = [make_scene(rng, SPEC) for _ in range(2)]
    xs, ys = build_target_pool(cfg, scenes, 128)
    grid = detector.grid_size(cfg)
    scale = cfg.input_size / 128
    ex, ey = [], []
    for img, boxes, classes in scenes:
        g = img.shape[0] // 128
        t = np.asarray(tiling.resize_tiles(
            tiling.tile_image(jnp.asarray(img), 128), cfg.input_size))
        for ty in range(g):
            for tx in range(g):
                b, c = clip_boxes_to_tile(boxes, classes, tx, ty, 128)
                ex.append(t[ty * g + tx])
                ey.append(boxes_to_targets(b, c, grid, cfg.n_anchors,
                                           cfg.n_classes, cfg.input_size,
                                           scale))
    np.testing.assert_array_equal(xs, np.stack(ex))
    np.testing.assert_allclose(ys, np.stack(ey), atol=1e-6)
