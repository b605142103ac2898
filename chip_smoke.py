#!/usr/bin/env python3
"""Smoke run of the TargetFuse cascade on a TPU, at published widths.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # the sharded fleet on four chips

One chip: builds ``targetfuse-space`` and ``targetfuse-ground`` at their
published configs (416-px input, widths 16..512 / 32..1024, two blocks
per stage) from seeded weights, generates 1024-px ``xview`` scenes from
a seed, and drives the normal entry points: ``Mission(...).run(frames)``
for all five policies and one ``run_scenario`` fleet round with a
contact plan. It checks, on the chip:

(a) each main-path Pallas kernel (``tile_moments``, ``kmeans_assign``,
    ``iou_matrix``) against ``kernels/ref.py`` at the shapes the
    pipeline feeds it;
(b) per-tile counts and summaries of the Pallas-dispatched run against
    the same run routed through the reference kernels.

``--chips 4`` runs only the sharded fleet (``mesh=sats_mesh(4)``)
against the unsharded fleet on the same scenario, and, where they
differ, the sharded fleet with ``strict_parity=True``.

Everything is reported on earlier lines; the last line is one JSON
object ``{"ok": true, "device": {...}}``, printed only when every check
passed. Exits non-zero, printing no result, off the TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SCORE_THRESH = 0.25
NMS_IOU = 0.25  # cascade.count_tiles default
TILE_PX = 128
# Seeded counters: detector.init weights, then the 1x1 head is rescaled
# and biased. At init the trunk's features reach the head at ~1e-3, so
# every cell would score ~0.5/n_classes, below any threshold, and NMS
# would see nothing. The gain brings the head's logit spread to ~1.5
# (measured on xview tiles); the objectness bias puts a minority of
# cells over SCORE_THRESH, so NMS both keeps and suppresses boxes.
COUNTERS = {  # name -> (init seed, head gain, objectness bias)
    "targetfuse-space": (0, 3000.0, -3.0),
    "targetfuse-ground": (1, 8000.0, -2.0),
}
SCENE = "xview"  # 1024-px scenes: 64 tiles per frame
SCENES, REVISITS, SCENE_SEED = 2, 2, 7  # 4 frames = one 256-tile bucket
# Tolerances, stated before any chip run (the CPU kernel tests' bounds):
TOL_MOMENTS = 1e-4    # abs and rel, each of mean / stddev / skew
TOL_KMEANS_D2 = 1e-4  # abs and rel; assignments must be equal
TOL_IOU = 1e-5        # abs
# Pallas vs reference mission runs: per-tile counts and summaries equal.


def log(msg: str) -> None:
    print(msg, flush=True)


class Meter:
    """Per-phase wall time, backend compile time and persistent-cache
    hits/misses, read from jax.monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        self.totals = {"compile_s": 0.0, "hits": 0, "misses": 0}

    def _on_duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, h0, m0 = self.compile_s, self.hits, self.misses
        t0 = time.perf_counter()
        try:
            yield
        finally:
            wall = time.perf_counter() - t0
            c, h, m = self.compile_s - c0, self.hits - h0, self.misses - m0
            self.totals["compile_s"] += c
            self.totals["hits"] += h
            self.totals["misses"] += m
            log(f"phase {name}: wall {wall:.2f} s, backend compile {c:.2f} s, "
                f"cache hits {h}, misses {m}")


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        log(f"check {name}: {'PASS' if ok else 'FAIL'}"
            + (f" ({detail})" if detail else ""))
        if not ok:
            self.failed.append(name)
        return ok


@contextlib.contextmanager
def reference_kernels():
    """Route every kernel call through kernels/ref.py (the plain XLA
    path). Dispatch is decided at trace time, so compiled programs are
    dropped on the way in and out."""
    import jax
    from repro.kernels import ops
    saved = ops._on_tpu
    ops._on_tpu = lambda: False
    jax.clear_caches()
    try:
        yield
    finally:
        ops._on_tpu = saved
        jax.clear_caches()


def seeded_counter(name: str):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import detector

    seed, gain, obj_bias = COUNTERS[name]
    cfg = get_config(name)
    params = detector.init(jax.random.PRNGKey(seed), cfg)
    bias = np.zeros((cfg.n_anchors, 5 + cfg.n_classes), np.float32)
    bias[:, 4] = obj_bias
    params["head_w"] = params["head_w"] * gain
    params["head_b"] = jnp.asarray(bias.reshape(-1))
    return params, cfg


def make_frames():
    import numpy as np
    from repro.data.synthetic import DATASETS, make_scene, revisit_frames
    rng = np.random.default_rng(SCENE_SEED)
    frames = []
    for _ in range(SCENES):
        img, b, c = make_scene(rng, DATASETS[SCENE])
        frames += revisit_frames(rng, img, b, c, REVISITS)
    return frames


def tiles_per_frame() -> int:
    from repro.data.synthetic import DATASETS
    return (-(-DATASETS[SCENE].scene_px // TILE_PX)) ** 2


def deviation(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    d = np.abs(a - b)
    return float(d.max()), float((d / np.maximum(np.abs(b), 1e-30)).max())


def histogram(values) -> str:
    import numpy as np
    v, n = np.unique(np.asarray(values).astype(np.int64), return_counts=True)
    return " ".join(f"{a}:{b}" for a, b in zip(v, n))


def check_kernels(check, space, prep):
    """(a): each Pallas kernel against ref.py at pipeline shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro.core.dedup as dd
    from repro.kernels import ref
    from repro.kernels.iou import iou_matrix
    from repro.kernels.kmeans_assign import kmeans_assign
    from repro.kernels.tile_moments import tile_moments
    from repro.models import detector

    tiles = prep.tiles_sp  # (256, 416, 416, 3): one frame bucket
    got = np.asarray(jax.jit(tile_moments)(tiles))
    want = np.asarray(jax.jit(ref.tile_moments)(tiles))
    c = tiles.shape[-1]
    ok, parts = True, []
    for j, part in enumerate(("mean", "stddev", "skew")):
        g, w = got[:, j * c:(j + 1) * c], want[:, j * c:(j + 1) * c]
        ab, rel = deviation(g, w)
        ok &= bool(np.allclose(g, w, atol=TOL_MOMENTS, rtol=TOL_MOMENTS))
        parts.append(f"{part} max abs {ab:.3e} rel {rel:.3e}")
    check(f"tile_moments {tuple(tiles.shape)} vs ref", ok, "; ".join(parts))

    # the dedup call shapes: (n_pad, 9) features against one centroid
    # (k-means++ picks) and against the k_pad table (Lloyd/finalize)
    n = prep.n
    n_pad, k_pad = dd._buckets_for(n, n // 2)
    m_pad = dd._pad_rows(prep.moments, n, n_pad)
    x, cent = dd._dedup_padded_core(m_pad, jnp.int32(n), jnp.int32(n // 2),
                                    jax.random.PRNGKey(0), k_pad=k_pad,
                                    iters=10)
    xla_default = jax.jit(lambda x, c: jnp.argmin(
        jnp.sum(x * x, -1, keepdims=True) - 2.0 * x @ c.T
        + jnp.sum(c * c, -1)[None, :], axis=-1))
    for cents in (cent[:1], cent):
        a1, d1 = jax.jit(kmeans_assign)(x, cents)
        a2, d2 = jax.jit(ref.kmeans_assign)(x, cents)
        a1, a2 = np.asarray(a1), np.asarray(a2)
        flips = int((a1 != a2).sum())
        ab, rel = deviation(d1, d2)
        ok = flips == 0 and bool(np.allclose(
            np.asarray(d1), np.asarray(d2), atol=TOL_KMEANS_D2,
            rtol=TOL_KMEANS_D2))
        default_flips = int((np.asarray(xla_default(x, cents)) != a2).sum())
        check(f"kmeans_assign x{tuple(x.shape)} c{tuple(cents.shape)} vs ref",
              ok, f"assignment flips {flips}/{n_pad}, d2 max abs {ab:.3e} "
                  f"rel {rel:.3e}; XLA matmul at default precision would "
                  f"flip {default_flips}")

    raw = jax.jit(detector.forward, static_argnums=1)(space[0], space[1],
                                                      tiles[:64])
    boxes, scores = detector.decode(raw, space[1])
    _, top = jax.lax.top_k(scores, 128)
    top_b = jnp.take_along_axis(boxes, top[..., None], axis=1)  # (64,128,4)
    i1 = np.asarray(jax.jit(jax.vmap(lambda b: iou_matrix(b, b)))(top_b))
    i2 = np.asarray(jax.jit(jax.vmap(lambda b: ref.iou_matrix(b, b)))(top_b))
    ab, _ = deviation(i1, i2)
    flips = int(((i1 > NMS_IOU) != (i2 > NMS_IOU)).sum())
    check(f"iou_matrix vmap{tuple(top_b.shape)} vs ref",
          ab <= TOL_IOU and flips == 0,
          f"max abs {ab:.3e}, flips at nms_iou={NMS_IOU}: {flips}")

    above = np.asarray((scores > SCORE_THRESH).sum(-1))
    cnt, _ = jax.jit(detector.count_and_confidence, static_argnums=1,
                     static_argnames=("score_thresh", "iou_thresh"))(
        raw, space[1], score_thresh=SCORE_THRESH, iou_thresh=NMS_IOU)
    cnt = np.asarray(cnt)
    log(f"nms on 64 tiles: {int(above.sum())} boxes over score_thresh "
        f"{SCORE_THRESH}, {int(cnt.sum())} kept, "
        f"{int(np.minimum(above, 128).sum() - cnt.sum())} suppressed")
    check("nms keeps and suppresses",
          0 < cnt.sum() < np.minimum(above, 128).sum())


def run_arm(space, ground, frames):
    """The five policies plus the dedup and onboard-count stages on
    their own, under whatever kernel dispatch is in force."""
    import jax
    import numpy as np
    import repro.core.dedup as dd
    from repro.core.cascade import count_tiles_batched
    from repro.core.mission import Mission
    from repro.core.pipeline import PipelineConfig
    from repro.core.policies import available_policies
    from repro.core import engine

    out = {"missions": {}}
    p = engine.prepare_frames(frames, TILE_PX, space[1].input_size,
                              ground[1].input_size)
    out["moments"] = np.asarray(p.moments[:p.n])
    res = dd.dedup_from_moments(p.moments[:p.n], p.n // 2,
                                jax.random.PRNGKey(0))
    out["dedup_assign"] = np.asarray(res.assign)
    out["space_counts"] = count_tiles_batched(
        space[0], space[1], p.tiles_sp[:p.n], score_thresh=SCORE_THRESH)[0]
    for method in available_policies():
        pcfg = PipelineConfig(method=method, score_thresh=SCORE_THRESH)
        r = Mission(space, ground, pcfg).run(frames)
        out["missions"][method] = (np.asarray(r.per_tile_pred), r.summary())
    return out


def compare_arms(check, pallas, refk):
    import numpy as np
    ab, rel = deviation(pallas["moments"], refk["moments"])
    log(f"arms: capture moments max abs {ab:.3e} rel {rel:.3e}")
    flips = int((pallas["dedup_assign"] != refk["dedup_assign"]).sum())
    log(f"arms: dedup assignment flips {flips}/{len(refk['dedup_assign'])}")
    check("onboard counts, all tiles: pallas == ref",
          np.array_equal(pallas["space_counts"], refk["space_counts"]),
          f"{int((pallas['space_counts'] != refk['space_counts']).sum())} "
          f"tiles differ")
    for method, (pred, summ) in pallas["missions"].items():
        pred_r, summ_r = refk["missions"][method]
        differ = int((pred != pred_r).sum())
        fields = sorted(k for k in summ if summ[k] != summ_r[k])
        check(f"mission {method}: pallas == ref", differ == 0 and not fields,
              f"{differ}/{len(pred)} tiles differ, summary fields "
              f"differing: {fields or 'none'}; cmae {summ['cmae']:.4f}")


def fleet_scenario(n_sats: int):
    from repro.data.scenarios import (FleetScenarioSpec, GroundStation,
                                      generate_scenario)
    from repro.data.synthetic import DATASETS
    return generate_scenario(FleetScenarioSpec(
        n_sats=n_sats, n_rounds=1, frames_per_pass=2,
        stations=(GroundStation("gs0"),
                  GroundStation("gs1", bandwidth_mbps=30.0, contact_s=240.0)),
        scene_mix=(DATASETS[SCENE],), seed=11))


def check_fleet_results(check, label, results, n_tiles):
    import numpy as np
    preds = [np.asarray(r.per_tile_pred) for r in results]
    total = sum(len(p) for p in preds)
    ok = total == n_tiles and all(
        np.all(np.isfinite(p)) and np.all(p >= 0) for p in preds)
    check(f"{label}: per-tile predictions finite, one per tile", ok,
          f"{total} tiles, pred {sum(float(p.sum()) for p in preds):.0f}, "
          f"cmae per sat {[round(r.cmae, 4) for r in results]}")


def fleet_parity(a, b):
    """(max |pred| deviation, sats whose summaries differ)."""
    import numpy as np
    dev = max((float(np.max(np.abs(x.per_tile_pred - y.per_tile_pred)))
               for x, y in zip(a, b) if x.per_tile_pred.size), default=0.0)
    sats = [i for i, (x, y) in enumerate(zip(a, b))
            if x.summary() != y.summary()]
    return dev, sats


def report_programs(check, space, ground, prep):
    """Compile each main-path program at the shapes the run used (a
    persistent-cache hit after the run) and report tpu_custom_call and
    the counting programs' memory."""
    import jax
    import jax.numpy as jnp
    import repro.core.dedup as dd
    from repro.core import cascade, engine
    from repro.data.synthetic import DATASETS

    n = prep.n
    n_pad, k_pad = dd._buckets_for(n, n // 2)
    px = DATASETS[SCENE].scene_px
    imgs = jax.ShapeDtypeStruct((engine.FRAME_BUCKET, px, px, 3),
                                jnp.float32)
    m = jax.ShapeDtypeStruct((n_pad, 9), jnp.float32)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    key = jax.random.PRNGKey(0)
    progs = [
        ("capture (frame program)", engine._frame_program.lower(
            imgs, tile_size=TILE_PX, sp_size=space[1].input_size,
            gd_size=ground[1].input_size, with_stats=True)),
        ("dedup core", dd._dedup_padded_core.lower(
            m, i32, i32, key, k_pad=k_pad, iters=10)),
        ("dedup finalize", dd._dedup_finalize.lower(
            m, jax.ShapeDtypeStruct((k_pad, 9), jnp.float32), i32)),
        ("dedup core, fleet (vmapped)", dd._dedup_multi_core.lower(
            jax.ShapeDtypeStruct((1, n_pad, 9), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((1, 2), jnp.uint32), k_pad=k_pad, iters=10)),
    ]
    for label, (params, cfg) in (("space", space), ("ground", ground)):
        s = cfg.input_size
        tiles = jax.ShapeDtypeStruct((64, s, s, 3), jnp.float32)
        progs.append((f"count {label} x64", cascade.count_tiles.lower(
            params, cfg, tiles, SCORE_THRESH, NMS_IOU)))
    for name, lowered in progs:
        compiled = lowered.compile()
        has = "tpu_custom_call" in compiled.as_text()
        check(f"program {name} calls a Pallas kernel (tpu_custom_call)", has)
        if name.startswith("count"):
            ma = compiled.memory_analysis()
            log(f"memory {name}: argument {ma.argument_size_in_bytes} B, "
                f"output {ma.output_size_in_bytes} B, temp "
                f"{ma.temp_size_in_bytes} B, generated code "
                f"{ma.generated_code_size_in_bytes} B")


def one_chip(meter, check):
    import jax
    import numpy as np
    from repro.core import engine
    from repro.core.fleet import run_scenario
    from repro.core.pipeline import PipelineConfig

    with meter.phase("counters"):
        space = seeded_counter("targetfuse-space")
        ground = seeded_counter("targetfuse-ground")
        jax.block_until_ready((space[0], ground[0]))
        for p, cfg in (space, ground):
            log(f"counter {cfg.name}: input {cfg.input_size} px, widths "
                f"{cfg.widths}, {cfg.n_blocks_per_stage} blocks/stage, "
                f"{cfg.n_params} params")
    with meter.phase("scenes"):
        frames = make_frames()
        log(f"scenes: {len(frames)} {SCENE} frames of {frames[0][0].shape[0]} "
            f"px, {TILE_PX}-px tiles")
    with meter.phase("capture"):
        prep = engine.prepare_frames(frames, TILE_PX, space[1].input_size,
                                     ground[1].input_size)
        jax.block_until_ready(prep.tiles_gd)
    with meter.phase("kernels vs ref"):
        check_kernels(check, space, prep)
    with meter.phase("missions (pallas)"):
        pallas = run_arm(space, ground, frames)
    log(f"onboard counts per tile, histogram count:tiles: "
        f"{histogram(pallas['space_counts'])}")
    for method, (pred, s) in pallas["missions"].items():
        log(f"mission {method}: cmae {s['cmae']:.4f}, pred "
            f"{s['total_pred']:.0f}, true {s['total_true']:.0f}, processed "
            f"{s['tiles_processed_space']}, downlinked "
            f"{s['tiles_downlinked']}/{s['tiles_total']}; per-tile pred "
            f"histogram {histogram(pred)}")
    with meter.phase("fleet round"):
        sc = fleet_scenario(3)
        pcfg = PipelineConfig(method="targetfuse", score_thresh=SCORE_THRESH)
        res_f, fleet = run_scenario(space, ground, pcfg, sc, fleet=True)
        n_tiles = sc.n_frames * tiles_per_frame()
        check_fleet_results(check, "fleet round", res_f, n_tiles)
        s = fleet.summary()
        log(f"fleet: {s['windows_served']} windows served, "
            f"{s['tiles_downlinked']} tiles downlinked, bytes "
            f"{s['bytes_spent']:.0f}/{s['bytes_budget']:.0f}")
        res_l, _ = run_scenario(space, ground, pcfg, sc, fleet=False)
        dev, sats = fleet_parity(res_f, res_l)
        log(f"fleet vs looped missions (reported, not gated): max pred "
            f"deviation {dev}, summaries differ for sats {sats or 'none'}")
    with meter.phase("missions (reference kernels)"):
        with reference_kernels():
            refk = run_arm(space, ground, frames)
    compare_arms(check, pallas, refk)
    with meter.phase("programs"):
        report_programs(check, space, ground, prep)
    return np.all(np.isfinite(pallas["space_counts"]))


def four_chips(meter, check):
    import jax
    from repro.core.fleet import run_scenario
    from repro.core.fleet_sharding import sats_mesh
    from repro.core.pipeline import PipelineConfig

    with meter.phase("counters"):
        space = seeded_counter("targetfuse-space")
        ground = seeded_counter("targetfuse-ground")
        jax.block_until_ready((space[0], ground[0]))
    with meter.phase("scenario"):
        sc = fleet_scenario(4)
        n_tiles = sc.n_frames * tiles_per_frame()
        log(f"scenario: 4 satellites, {sc.n_frames} {SCENE} frames, "
            f"{n_tiles} tiles")
    pcfg = PipelineConfig(method="targetfuse", score_thresh=SCORE_THRESH)
    runs = {}
    for label, kw in (("unsharded", {}),
                      ("sharded", {"mesh": sats_mesh(4)})):
        with meter.phase(f"fleet {label}"):
            runs[label], fl = run_scenario(space, ground, pcfg, sc,
                                           fleet=True, **kw)
            check_fleet_results(check, f"fleet {label}", runs[label], n_tiles)
            log(f"fleet {label}: devices {fl.sharding.n_devices}")
    dev, sats = fleet_parity(runs["sharded"], runs["unsharded"])
    log(f"sharded vs unsharded: max pred deviation {dev}, summaries "
        f"differ for sats {sats or 'none'}")
    parity = dev == 0.0 and not sats
    if not parity:
        with meter.phase("fleet sharded strict_parity"):
            strict, _ = run_scenario(space, ground, pcfg, sc, fleet=True,
                                     mesh=sats_mesh(4), strict_parity=True)
        dev_s, sats_s = fleet_parity(strict, runs["unsharded"])
        log(f"sharded strict_parity vs unsharded: max pred deviation "
            f"{dev_s}, summaries differ for sats {sats_s or 'none'}")
        parity = dev_s == 0.0 and not sats_s
    check("sharded fleet bit-equal to unsharded (plain or strict_parity)",
          parity)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        from repro.kernels import ops  # noqa: F401
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} visible",
              file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind}, {len(devs)} visible, "
        f"jax {jax.__version__}")
    log(f"compile cache: {compile_cache.enable()}")
    meter, check = Meter(), Checks()
    t0 = time.perf_counter()
    ok = (one_chip if args.chips == 1 else four_chips)(meter, check)
    t = meter.totals
    log(f"total: wall {time.perf_counter() - t0:.2f} s, backend compile "
        f"{t['compile_s']:.2f} s, compile-cache hits {t['hits']}, "
        f"misses {t['misses']}")
    if check.failed or not ok:
        log(f"FAILED: {check.failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
