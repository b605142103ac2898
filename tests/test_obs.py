"""Tests for repro.core.obs — the program's span and counter recorder:
the null context when off, nesting and parents per thread, counter
snapshots, compile attribution, the span tree of a tiny Mission, the
capture's staging and transfer counters, the count batches' padding
counters, and the transfer counters folded in from repro.core.xfer."""
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.xfer as xfer
from repro.core import obs
from repro.core.cascade import _tier_batch


@pytest.fixture
def recorder():
    obs.enable()
    try:
        yield obs
    finally:
        obs.disable()


def _delta(after, before):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def test_off_records_nothing_and_shares_the_null_context():
    assert not obs.enabled()
    before = obs.records()  # an earlier recording stays readable
    a, b = obs.span("a"), obs.span("b")
    assert a is b
    with a:
        with b:
            pass
    assert obs.records() == before


def test_nesting_and_parents(recorder):
    with obs.span("outer"):
        with obs.span("inner"):
            with obs.span("leaf"):
                pass
        with obs.span("second"):
            pass
    parents = {r.name: r.parent for r in obs.records()}
    assert parents == {"outer": None, "inner": "outer", "leaf": "inner",
                       "second": "outer"}
    ev = {n: (s, e) for n, s, e in obs.events()}
    assert ev["outer"][0] <= ev["inner"][0] <= ev["leaf"][0]
    assert ev["leaf"][1] <= ev["inner"][1] <= ev["second"][0]
    assert ev["second"][1] <= ev["outer"][1]


def test_disable_stops_recording_and_keeps_records(recorder):
    with obs.span("kept"):
        pass
    obs.disable()
    with obs.span("dropped"):
        pass
    assert [n for n, _, _ in obs.events()] == ["kept"]
    obs.enable()
    assert obs.events() == []


def test_worker_thread_spans_do_not_nest_under_the_foreground(recorder):
    def work():
        with obs.span("worker"):
            with obs.span("worker.inner"):
                pass

    with obs.span("foreground"):
        t = threading.Thread(target=work)
        t.start()
        t.join()
    recs = {r.name: r for r in obs.records()}
    assert recs["worker"].parent is None
    assert recs["worker.inner"].parent == "worker"
    assert recs["worker"].thread != recs["foreground"].thread
    # events() is the calling thread's only
    assert [n for n, _, _ in obs.events()] == ["foreground"]


def test_counters_snapshot_and_diff():
    before = obs.counters()
    obs.count("test.obs.a", 3)
    obs.count("test.obs.a")
    obs.count("test.obs.b", 5)
    snap = obs.counters()
    obs.count("test.obs.a", 10)  # a snapshot is a copy
    assert _delta(snap, before) == {"test.obs.a": 4, "test.obs.b": 5}
    obs.reset("test.obs.a", "test.obs.b")
    assert "test.obs.a" not in obs.counters()


def test_counters_and_spans_are_thread_safe(recorder):
    """More threads than cores, switching every few microseconds: no
    counter add and no span record is lost, and each thread's spans
    nest only under its own."""
    n_threads = (os.cpu_count() or 2) + 2
    obs.reset("test.obs.threads")
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def work():
        for _ in range(500):
            with obs.span("outer"):
                with obs.span("inner"):
                    obs.count("test.obs.threads")

    try:
        ts = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts)
    assert obs.counters()["test.obs.threads"] == 500 * n_threads
    recs = obs.records()
    assert len(recs) == 2 * 500 * n_threads
    assert {r.parent for r in recs if r.name == "outer"} == {None}
    assert {r.parent for r in recs if r.name == "inner"} == {"outer"}
    obs.reset("test.obs.threads")


def test_compile_counted_under_the_innermost_span(recorder):
    before = obs.counters()

    def fresh(x):  # a new function object: never compiled before
        return x * 3.0 + 1.0

    with obs.span("step"):
        with obs.span("step.compiles"):
            jax.block_until_ready(jax.jit(fresh)(jnp.ones(7)))
    d = _delta(obs.counters(), before)
    assert d.get("compile@step.compiles", 0) >= 1
    assert "compile@step" not in d


def test_no_compile_counted_while_off():
    before = obs.counters()

    def fresh(x):
        return x - 2.0

    jax.block_until_ready(jax.jit(fresh)(jnp.ones(5)))
    assert not any(k.startswith("compile@")
                   for k in _delta(obs.counters(), before))


@pytest.fixture(scope="module")
def tiny_mission():
    from repro.configs import get_config, reduced
    from repro.core.pipeline import PipelineConfig
    from repro.data.synthetic import SceneSpec, make_scene, revisit_frames
    from repro.models import detector

    sp = reduced(get_config("targetfuse-space"))
    gd = reduced(get_config("targetfuse-ground"))
    space = (detector.init(jax.random.PRNGKey(0), sp), sp)
    ground = (detector.init(jax.random.PRNGKey(1), gd), gd)
    rng = np.random.default_rng(7)
    img, b, c = make_scene(rng, SceneSpec("mini", 384, (12, 18), (10, 24),
                                          cloud_fraction=0.2))
    frames = revisit_frames(rng, img, b, c, 3)
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    return space, ground, pcfg, frames


def _run_recorded(tiny_mission):
    from repro.core.mission import Mission
    space, ground, pcfg, frames = tiny_mission
    Mission(space, ground, pcfg).run(frames)  # compiles outside the record
    before = obs.counters()
    obs.enable()
    try:
        res = Mission(space, ground, pcfg).run(frames)
    finally:
        obs.disable()
    return res, obs.records(), _delta(obs.counters(), before)


def test_mission_span_tree(tiny_mission):
    res, recs, _ = _run_recorded(tiny_mission)
    assert res.tiles_processed_space > 0 and res.tiles_downlinked > 0
    parent = {}
    for r in recs:
        parent.setdefault(r.name, set()).add(r.parent)
    assert parent["mission.ingest"] == {None}
    assert parent["mission.contact"] == {None}
    for st in ("capture", "roi_filter", "dedup", "onboard_count"):
        assert parent["stage." + st] == {"mission.ingest"}
    for st in ("select", "downlink", "ground_recount", "aggregate"):
        assert parent["stage." + st] == {"mission.contact"}
    for sp in ("capture.fill", "capture.to_device", "capture.program",
               "capture.assemble"):
        assert parent[sp] == {"stage.capture"}
    for sp in ("dedup.gather", "dedup.program", "dedup.fetch"):
        assert parent[sp] == {"stage.dedup"}
    assert parent["count.space"] == {"stage.onboard_count"}
    assert parent["count.ground"] == {"stage.ground_recount"}
    for sp in ("count.gather", "count.pad", "count.program", "count.fetch"):
        assert parent[sp] == {"count.space", "count.ground"}
    # every span closes inside its parent
    spans = {}
    for r in recs:
        spans.setdefault(r.name, []).append(r)
    for r in recs:
        if r.parent is not None:
            assert any(p.start_ns <= r.start_ns and r.end_ns <= p.end_ns
                       for p in spans[r.parent])


def test_mission_counters_match_the_batch_arithmetic(tiny_mission):
    res, _, d = _run_recorded(tiny_mission)
    _, _, _, frames = tiny_mission
    n_sp, n_gd = res.tiles_processed_space, res.tiles_downlinked
    assert d["count.rows_real"] == n_sp + n_gd

    def computed(n):
        b = _tier_batch(n, 64)
        return -(-n // b) * b

    assert d["count.rows_computed"] == computed(n_sp) + computed(n_gd)
    nb = -(-len(frames) // 4) * 4  # engine.FRAME_BUCKET
    assert d["capture.frames_real"] == len(frames)
    assert d["capture.frames_computed"] == nb
    # the real frames cross one by one; pad frames are made on the device
    assert d["capture.h2d_bytes"] == len(frames) * frames[0][0].size * 4
    assert d.get("capture.frames_staged", 0) == 0


@pytest.mark.parametrize("where", ["host", "device", "mesh"])
def test_capture_stages_frames_only_on_the_mesh(where):
    """Off-mesh, frames go to the device without a host staging buffer
    (device frames cross nothing); the on-mesh path, here on a one-device
    ``sats`` mesh, still stacks its frames on the host."""
    from jax.sharding import Mesh

    from repro.core import engine
    from repro.core.fleet_sharding import SATS_AXIS, FleetSharding

    rng = np.random.default_rng(5)
    imgs = [rng.random((256, 256, 3), dtype=np.float32) for _ in range(5)]
    if where == "device":
        imgs = [jnp.asarray(img) for img in imgs]
    frames = [(img, np.zeros((0, 4), np.float32), np.zeros(0, np.int32))
              for img in imgs]
    sh = (FleetSharding(Mesh(np.asarray(jax.devices()[:1]), (SATS_AXIS,)))
          if where == "mesh" else None)
    before = obs.counters()
    engine.prepare_frames_multi([frames[:2], frames[2:]], 128, 64, 48,
                                sharding=sh)
    d = _delta(obs.counters(), before)
    frame_bytes = imgs[0].size * 4
    assert d["capture.frames_real"] == 5
    assert d["capture.frames_computed"] == 8
    staged, crossed = {"host": (0, 5 * frame_bytes), "device": (0, 0),
                       "mesh": (5, 8 * frame_bytes)}[where]
    assert d.get("capture.frames_staged", 0) == staged
    assert d.get("capture.h2d_bytes", 0) == crossed


def test_count_multi_counts_part_tier_and_chunk_padding():
    from repro.configs import get_config, reduced
    from repro.core.cascade import count_tiles_multi
    from repro.core.dedup import bucket_size
    from repro.models import detector

    cfg = reduced(get_config("targetfuse-space"))
    params = detector.init(jax.random.PRNGKey(0), cfg)
    tiles = jnp.zeros((16, cfg.input_size, cfg.input_size, 3), jnp.float32)
    sizes = (3, 0, 5)
    parts = [(tiles, np.arange(k)) for k in sizes]
    before = obs.counters()
    count_tiles_multi(params, cfg, parts, batch=8)
    d = _delta(obs.counters(), before)
    off = sum(bucket_size(k, 2) for k in sizes if k)  # 4 + 8
    b = _tier_batch(off, 8)
    assert d["count.rows_real"] == sum(sizes)
    assert d["count.rows_computed"] == -(-off // b) * b == 16


def test_transfer_stats_read_as_before_the_fold():
    xfer.clear_cache()
    xfer.reset_transfer_stats()
    try:
        assert xfer.transfer_stats() == {"device_puts": 0, "cache_reuses": 0}
        a = np.arange(6, dtype=np.int32)
        xfer.device_constant(a)
        xfer.device_constant(a)
        xfer.device_constant(np.zeros(1 << 15))  # above the cache cap
        xfer.record_transfer(2)
        assert xfer.transfer_stats() == {"device_puts": 4, "cache_reuses": 1}
        c = obs.counters()
        assert (c["xfer.device_puts"], c["xfer.cache_reuses"]) == (4, 1)
        xfer.reset_transfer_stats()
        assert xfer.transfer_stats() == {"device_puts": 0, "cache_reuses": 0}
    finally:
        xfer.clear_cache()
        xfer.reset_transfer_stats()
