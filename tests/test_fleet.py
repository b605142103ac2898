"""Fleet engine tests: differential exact-parity against looped
sequential Missions, stacked-ledger consistency, rotation semantics,
the batched capture/counting helpers, the vmapped multi-satellite dedup
core, and the sharded (device-mesh) fleet runtime.

The sharded differential gates need multiple host devices — run them via

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python -m pytest tests/test_fleet.py -k sharded

(scripts/ci.sh does); under plain tier-1 they skip.
"""
import jax
import numpy as np
import pytest

import repro.core.dedup as dd
from repro.core.cascade import (count_tiles_batched, count_tiles_multi)
from repro.core.engine import prepare_frames, prepare_frames_multi
from repro.core.fleet import Fleet, run_scenario
from repro.core.fleet_sharding import FleetSharding, sats_mesh
from repro.core.mission import Mission
from repro.core.pipeline import PipelineConfig
from repro.data.scenarios import (FleetScenarioSpec, GroundStation,
                                  generate_scenario)
from repro.data.synthetic import SceneSpec, make_scene, revisit_frames

METHODS = ("space_only", "ground_only", "tiansuan", "kodan", "targetfuse")

SCENE_A = SceneSpec("trackA", 384, (10, 18), (10, 24), cloud_fraction=0.25)
SCENE_B = SceneSpec("trackB", 256, (6, 12), (10, 20), cloud_fraction=0.2)


@pytest.fixture(scope="module")
def scenario():
    """3 satellites x 3 rounds, two stations with variable bandwidth,
    heterogeneous scene mixes, eclipse/sunlit harvest profile."""
    return generate_scenario(FleetScenarioSpec(
        n_sats=3, n_rounds=3, frames_per_pass=2,
        stations=(GroundStation("gs0"),
                  GroundStation("gs1", bandwidth_mbps=30.0, contact_s=240.0)),
        scene_mix=(SCENE_A, SCENE_B),
        eclipse_fraction=0.35, seed=11))


def _assert_same(a, b, ctx=""):
    np.testing.assert_array_equal(a.per_tile_pred, b.per_tile_pred,
                                  err_msg=f"{ctx}: per-tile preds differ")
    np.testing.assert_array_equal(a.per_tile_true, b.per_tile_true,
                                  err_msg=f"{ctx}: per-tile truth differs")
    assert a.summary() == b.summary(), (
        f"{ctx}: summaries differ:\n{a.summary()}\n{b.summary()}")


# ---------------------------------------------------------------------------
# the acceptance gate: fleet exact-equal to N sequential Missions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", METHODS)
def test_fleet_parity_all_policies(method, scenario, counters):
    space, ground = counters
    pcfg = PipelineConfig(method=method, score_thresh=0.25)
    got, fleet = run_scenario(space, ground, pcfg, scenario, fleet=True)
    want, missions = run_scenario(space, ground, pcfg, scenario, fleet=False)
    assert len(got) == len(want) == scenario.spec.n_sats
    for i, (a, b) in enumerate(zip(got, want)):
        _assert_same(a, b, f"{method} sat{i}")
    # the stacked fleet ledger matches every oracle Mission's scalar one
    for i, m in enumerate(missions):
        assert fleet.ledger.budget_j[i] == m.ledger.budget_j
        assert fleet.ledger.spent[i] == m.ledger.spent
        assert fleet.ledger.e_com[i] == m.ledger.e_com
        assert fleet.ledger.bytes_budget[i] == m.bytes_budget
        assert fleet.ledger.bytes_requested[i] == m.bytes_requested
        assert fleet.ledger.bytes_spent[i] == m.bytes_spent


def test_fleet_parity_reference_path(scenario, counters):
    """use_engine=False satellites fall back to sequential Mission
    ingest inside the fleet — still exact-equal to the oracle."""
    space, ground = counters
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25,
                          use_engine=False)
    got, _ = run_scenario(space, ground, pcfg, scenario, fleet=True)
    want, _ = run_scenario(space, ground, pcfg, scenario, fleet=False)
    for i, (a, b) in enumerate(zip(got, want)):
        _assert_same(a, b, f"reference sat{i}")


def test_fleet_heterogeneous_policies(scenario, counters):
    """A fleet mixing all five policies (one per satellite, wrapping)
    stays satellite-wise exact-equal to the per-policy oracles."""
    space, ground = counters
    n = scenario.spec.n_sats
    pcfgs = [PipelineConfig(method=METHODS[i % len(METHODS)],
                            score_thresh=0.25) for i in range(n)]
    got, _ = run_scenario(space, ground, pcfgs, scenario, fleet=True)
    want, _ = run_scenario(space, ground, pcfgs, scenario, fleet=False)
    for i, (a, b) in enumerate(zip(got, want)):
        _assert_same(a, b, f"mixed sat{i} ({pcfgs[i].method})")


def test_fleet_empty_pass_parity(counters):
    """A satellite with an empty pass in a round matches its oracle."""
    space, ground = counters
    rng = np.random.default_rng(2)
    img, b, c = make_scene(rng, SCENE_B)
    frames = revisit_frames(rng, img, b, c, 2)
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)

    fleet = Fleet(space, ground, pcfg, n_sats=2)
    fleet.ingest([frames, []])
    fleet.contact_round(windows=[(0, 2e6), (1, 2e6)])
    got = fleet.finalize()

    want = []
    for fr in (frames, []):
        m = Mission(space, ground, pcfg)
        m.ingest(fr)
        m.contact_window(2e6)
        want.append(m.finalize())
    for i, (a, b) in enumerate(zip(got, want)):
        _assert_same(a, b, f"empty-pass sat{i}")
    assert got[1].tiles_total == 0


# ---------------------------------------------------------------------------
# streaming semantics
# ---------------------------------------------------------------------------

def test_contact_round_rotation(counters):
    """Default contact_round serves satellites round-robin."""
    space, ground = counters
    rng = np.random.default_rng(3)
    img, b, c = make_scene(rng, SCENE_B)
    pcfg = PipelineConfig(method="space_only", score_thresh=0.25)
    fleet = Fleet(space, ground, pcfg, n_sats=3)

    served = []
    for _ in range(4):
        fleet.ingest([revisit_frames(rng, img, b, c, 1) for _ in range(3)])
        served += [sat for sat, _ in fleet.contact_round(stations=1)]
    assert served == [0, 1, 2, 0]
    # multi-station rounds serve distinct satellites
    fleet2 = Fleet(space, ground, pcfg, n_sats=3)
    fleet2.ingest([revisit_frames(rng, img, b, c, 1) for _ in range(3)])
    assert sorted(s for s, _ in fleet2.contact_round(stations=2)) == [0, 1]
    # more stations than satellites: the rotation wraps, windows are
    # never silently dropped (a sat may get two in one round)
    assert [s for s, _ in fleet2.contact_round(stations=4)] == [2, 0, 1, 2]


def test_contact_round_same_sat_twice_keeps_both_reports(counters):
    """Two windows to one satellite in a round (more stations than
    satellites) return BOTH reports in window order: the first drains
    the pending passes, the second finds nothing left."""
    space, ground = counters
    rng = np.random.default_rng(8)
    img, b, c = make_scene(rng, SCENE_B)
    pcfg = PipelineConfig(method="ground_only", score_thresh=0.25)
    fleet = Fleet(space, ground, pcfg, n_sats=1)
    fleet.ingest([revisit_frames(rng, img, b, c, 1)])
    tb = fleet.missions[0].tile_bytes
    reps = fleet.contact_round(windows=[(0, 2 * tb), (0, 2 * tb)])
    assert [sat for sat, _ in reps] == [0, 0]
    assert reps[0][1].segments == 1 and reps[0][1].tiles_downlinked == 2
    assert reps[1][1].segments == 0 and reps[1][1].bytes_spent == 0.0
    # same drain as the sequential oracle
    m = Mission(space, ground, pcfg)
    rng2 = np.random.default_rng(8)
    img2, b2, c2 = make_scene(rng2, SCENE_B)
    m.ingest(revisit_frames(rng2, img2, b2, c2, 1))
    m.contact_window(2 * tb)
    m.contact_window(2 * tb)
    _assert_same(fleet.finalize()[0], m.finalize(), "double-window sat0")


def test_fleet_finalize_drains_all(scenario, counters):
    space, ground = counters
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    fleet = Fleet(space, ground, pcfg, n_sats=scenario.spec.n_sats)
    for rnd in scenario.rounds:
        fleet.ingest(rnd.frames_per_sat(fleet.n_sats),
                     rnd.harvest_per_sat(fleet.n_sats))
    assert all(p > 0 for p in fleet.pending_segments)
    fleet.finalize()
    assert fleet.pending_segments == [0] * fleet.n_sats
    # idempotent, like Mission.finalize
    again = fleet.finalize()
    assert len(again) == fleet.n_sats


def test_fleet_summary_aggregates(scenario, counters):
    space, ground = counters
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    results, fleet = run_scenario(space, ground, pcfg, scenario, fleet=True)
    s = fleet.summary()
    assert s["n_sats"] == scenario.spec.n_sats
    assert s["tiles_total"] == sum(r.tiles_total for r in results)
    assert s["total_true"] == sum(r.total_true for r in results)
    assert s["bytes_spent"] <= s["bytes_budget"] + 1e-6
    # the energy cap governs compute: counting spend never overdraws the
    # granted harvest fleet-wide (capture is charged unconditionally —
    # imaging happens even through an eclipse round's zero grant — so
    # e_cap is outside the cap; remaining floors at 0)
    led = fleet.ledger
    assert (led.e_com <= led.budget_j + 1e-9).all()
    assert (led.remaining >= 0.0).all()


# ---------------------------------------------------------------------------
# batched helpers: shared-bucket capture and shared-batch counting
# ---------------------------------------------------------------------------

def test_prepare_frames_multi_matches_single(counters):
    space, ground = counters
    sp_size = space[1].input_size
    gd_size = ground[1].input_size
    rng = np.random.default_rng(5)
    workloads = []
    for k in (2, 1, 3):
        img, b, c = make_scene(rng, SCENE_A)
        workloads.append(revisit_frames(rng, img, b, c, k))
    workloads.insert(1, [])  # an idle satellite
    multi = prepare_frames_multi(workloads, 128, sp_size, gd_size)
    for w, got in zip(workloads, multi):
        want = prepare_frames(w, 128, sp_size, gd_size)
        assert got.n == want.n
        np.testing.assert_array_equal(np.asarray(got.tiles_sp)[:got.n],
                                      np.asarray(want.tiles_sp)[:want.n])
        np.testing.assert_array_equal(np.asarray(got.tiles_gd)[:got.n],
                                      np.asarray(want.tiles_gd)[:want.n])
        np.testing.assert_array_equal(np.asarray(got.moments)[:got.n],
                                      np.asarray(want.moments)[:want.n])
        np.testing.assert_array_equal(got.roi_std, want.roi_std)
        np.testing.assert_array_equal(got.true, want.true)


def test_prepare_frames_multi_mixed_resolutions(counters):
    """Workloads of different frame resolutions share buckets per
    resolution and still split back exactly."""
    space, ground = counters
    sp_size = space[1].input_size
    gd_size = ground[1].input_size
    rng = np.random.default_rng(6)
    wa, wb = [], []
    ia, ba, ca = make_scene(rng, SCENE_A)
    ib, bb, cb = make_scene(rng, SCENE_B)
    wa = revisit_frames(rng, ia, ba, ca, 2)
    wb = revisit_frames(rng, ib, bb, cb, 3)
    multi = prepare_frames_multi([wa, wb], 128, sp_size, gd_size)
    for w, got in zip((wa, wb), multi):
        want = prepare_frames(w, 128, sp_size, gd_size)
        assert got.n == want.n
        np.testing.assert_array_equal(np.asarray(got.tiles_sp)[:got.n],
                                      np.asarray(want.tiles_sp)[:want.n])
        np.testing.assert_array_equal(got.roi_std, want.roi_std)
        np.testing.assert_array_equal(got.true, want.true)


def test_dedup_multi_matches_sequential_core():
    """The vmapped multi-satellite dedup core is bit-equal (documented
    tolerance: 0.0 on CPU) to per-satellite `dedup_from_moments` across
    mixed shape buckets, paddings, and keys."""
    import jax.numpy as jnp
    rng = np.random.default_rng(9)
    shapes = ((128, 100, 10), (128, 128, 20), (256, 200, 4), (128, 37, 5),
              (128, 100, 10))  # a duplicate workload shares its bucket
    parts = [(jnp.asarray(rng.random((n_pad, 9)).astype(np.float32)), k,
              jax.random.PRNGKey(k), n)
             for n_pad, n, k in shapes]
    got = dd.dedup_multi(parts)
    for (mo, k, key, n), res in zip(parts, got):
        want = dd.dedup_from_moments(mo, k, key, n=n)
        for f in res._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(res, f)), np.asarray(getattr(want, f)),
                err_msg=f"dedup_multi.{f} diverges at n={n} k={k}")


def test_fleet_strict_parity_matches_batched_dedup(scenario, counters):
    """strict_parity=True (sequential per-sat dedup core) and the
    default batched dedup produce identical fleets on CPU — the
    documented zero-tolerance parity story."""
    space, ground = counters
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    got, fl = run_scenario(space, ground, pcfg, scenario, fleet=True)
    want, fs = run_scenario(space, ground, pcfg, scenario, fleet=True,
                            strict_parity=True)
    assert fl.summary()["dedup_batched"] is True
    assert fs.summary()["dedup_batched"] is False
    for i, (a, b) in enumerate(zip(got, want)):
        _assert_same(a, b, f"strict-parity sat{i}")


def test_fleet_summary_reports_runtime_facts(scenario, counters):
    space, ground = counters
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    results, fleet = run_scenario(space, ground, pcfg, scenario, fleet=True)
    s = fleet.summary()
    assert s["n_devices"] == 1  # no mesh attached
    assert s["dedup_batched"] is True
    assert s["ingest_s"] > 0.0
    assert s["tiles_per_s"] == pytest.approx(
        sum(r.tiles_total for r in results) / s["ingest_s"])
    assert s["tiles_per_s_per_sat"] == pytest.approx(
        s["tiles_per_s"] / scenario.spec.n_sats)


def test_count_tiles_batched_size_tiers_match_direct(counters):
    """Tiered small-n batching is per-sample: every tier boundary yields
    the same counts as the one-shot full-batch forward."""
    from repro.core.cascade import _tier_batch, count_tiles
    (params, cfg), _ = counters
    assert [_tier_batch(n, 64) for n in (1, 8, 9, 16, 17, 63, 64, 65)] == \
        [8, 8, 16, 16, 32, 64, 64, 64]
    rng = np.random.default_rng(11)
    tiles = rng.random((70, cfg.input_size, cfg.input_size, 3)
                       ).astype(np.float32)
    for n in (1, 5, 8, 9, 16, 17, 33, 63, 64, 65, 70):
        import jax.numpy as jnp
        want_c, want_f = count_tiles(params, cfg, jnp.asarray(tiles[:n]),
                                     0.25)
        got_c, got_f = count_tiles_batched(params, cfg, tiles,
                                           idx=np.arange(n),
                                           score_thresh=0.25)
        np.testing.assert_array_equal(got_c, np.asarray(want_c))
        np.testing.assert_array_equal(got_f, np.asarray(want_f))


def test_count_tiles_multi_matches_batched(counters):
    (params, cfg), _ = counters
    rng = np.random.default_rng(7)
    tiles_a = rng.random((40, cfg.input_size, cfg.input_size, 3),
                         ).astype(np.float32)
    tiles_b = rng.random((16, cfg.input_size, cfg.input_size, 3),
                         ).astype(np.float32)
    parts = [(tiles_a, np.arange(0, 40, 2)),
             (tiles_b, np.array([], np.int64)),
             (tiles_b, np.array([3, 0, 15]))]
    multi = count_tiles_multi(params, cfg, parts, score_thresh=0.25)
    assert len(multi) == len(parts)
    for (tiles, idx), (c, f) in zip(parts, multi):
        want_c, want_f = count_tiles_batched(params, cfg, tiles, idx=idx,
                                             score_thresh=0.25)
        np.testing.assert_array_equal(c, want_c)
        np.testing.assert_array_equal(f, want_f)


# ---------------------------------------------------------------------------
# sharded fleet runtime: device-mesh differential gates
# (need >= 4 host devices: XLA_FLAGS=--xla_force_host_platform_device_count=4)
# ---------------------------------------------------------------------------

requires_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="sharded gates need XLA_FLAGS="
           "--xla_force_host_platform_device_count=4 (scripts/ci.sh sets it)")


def _assert_lanes_equal(a: Fleet, b: Fleet, ctx=""):
    for f in ("budget_j", "e_cap", "e_com", "e_agg", "e_down",
              "bytes_budget", "bytes_requested", "bytes_spent"):
        np.testing.assert_array_equal(
            getattr(a.ledger, f)[:a.n_sats], getattr(b.ledger, f)[:b.n_sats],
            err_msg=f"{ctx}: ledger lane {f} differs")


def test_off_mesh_sharding_is_noop():
    """FleetSharding without a mesh degrades to identity (the ctx.py
    pattern): single-device fleets run the pre-sharding code path."""
    sh = FleetSharding(None)
    assert not sh.on_mesh and sh.n_devices == 1
    assert sh.pad(5) == 5
    arr = np.arange(6.0)
    assert sh.shard(arr) is arr and sh.device_put(arr) is arr
    assert sats_mesh(1) is None


@requires_mesh
@pytest.mark.parametrize("method", METHODS)
def test_fleet_sharded_parity_all_policies(method, scenario, counters):
    """The acceptance gate: the mesh-sharded fleet (4 host devices) is
    bit-equal to the single-device fleet — per-tile preds, summaries,
    and ledger lanes — for every registered policy."""
    space, ground = counters
    mesh = sats_mesh(4)
    pcfg = PipelineConfig(method=method, score_thresh=0.25)
    got, fs = run_scenario(space, ground, pcfg, scenario, fleet=True,
                           mesh=mesh)
    want, f1 = run_scenario(space, ground, pcfg, scenario, fleet=True)
    assert fs.summary()["n_devices"] == 4
    for i, (a, b) in enumerate(zip(got, want)):
        _assert_same(a, b, f"sharded {method} sat{i}")
    _assert_lanes_equal(fs, f1, f"sharded {method}")


@requires_mesh
def test_fleet_sharded_uneven_lane_padding(counters):
    """n_sats=6 over 4 devices: lane padding to 8 never perturbs real
    lanes — preds, summaries, and all ledger lanes match the unsharded
    fleet, and pad lanes stay zero."""
    space, ground = counters
    mesh = sats_mesh(4)
    sc = generate_scenario(FleetScenarioSpec(
        n_sats=6, n_rounds=2, frames_per_pass=1,
        stations=(GroundStation("gs0"),
                  GroundStation("gs1", bandwidth_mbps=30.0)),
        scene_mix=(SCENE_A, SCENE_B), eclipse_fraction=0.35, seed=13))
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    got, fs = run_scenario(space, ground, pcfg, sc, fleet=True, mesh=mesh)
    want, f1 = run_scenario(space, ground, pcfg, sc, fleet=True)
    assert fs.ledger.n_lanes == 8 and f1.ledger.n_lanes == 6
    for i, (a, b) in enumerate(zip(got, want)):
        _assert_same(a, b, f"uneven sat{i}")
    _assert_lanes_equal(fs, f1, "uneven")
    for f in ("budget_j", "e_cap", "e_com", "e_agg", "e_down",
              "bytes_budget", "bytes_requested", "bytes_spent"):
        assert (getattr(fs.ledger, f)[6:] == 0.0).all(), \
            f"pad lanes of {f} were written"
    ss, s1 = fs.summary(), f1.summary()
    assert ss["n_devices"] == 4 and s1["n_devices"] == 1
    for s in (ss, s1):  # wall-clock/throughput legitimately differ
        for key in ("n_devices", "ingest_s", "tiles_per_s",
                    "tiles_per_s_per_sat", "contact_s", "windows_per_s",
                    "bytes_downlinked_per_s", "recount_s", "recount_wait_s",
                    "recount_hidden_frac", "ingest_dispatch_s",
                    "device_compute_s", "host_fetch_s",
                    "ingest_hidden_frac"):
            s.pop(key)
    assert ss == s1


@requires_mesh
def test_fleet_sharded_matches_oracle_missions(scenario, counters):
    """Transitively: sharded fleet == looped sequential Missions."""
    space, ground = counters
    mesh = sats_mesh(4)
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    got, _ = run_scenario(space, ground, pcfg, scenario, fleet=True,
                          mesh=mesh)
    want, _ = run_scenario(space, ground, pcfg, scenario, fleet=False)
    for i, (a, b) in enumerate(zip(got, want)):
        _assert_same(a, b, f"sharded-vs-oracle sat{i}")


@requires_mesh
@pytest.mark.parametrize("strict", [False, True])
def test_fleet_sharded_kernel_programs_get_one_device(counters, monkeypatch,
                                                      strict):
    """On the TPU a program that calls a Pallas kernel outside
    ``map_lanes`` cannot be partitioned, so every such program of the
    sharded fleet (capture, sequential dedup, chunk counting) must be
    handed single-device operands — the CPU stand-in for that chip
    error. One scene size, so capture takes the sharded stacked path."""
    import repro.core.cascade as cascade
    import repro.core.engine as engine
    spanning = []

    def guard(name, fn):
        def run(*args, **kw):
            spanning.extend(
                name for x in jax.tree.leaves((args, kw))
                if hasattr(x, "sharding") and len(x.sharding.device_set) > 1)
            return fn(*args, **kw)
        return run

    for mod, name in ((cascade, "count_tiles"), (engine, "_frame_program"),
                      (dd, "_dedup_padded_core"), (dd, "_dedup_finalize")):
        monkeypatch.setattr(mod, name, guard(name, getattr(mod, name)))
    space, ground = counters
    sc = generate_scenario(FleetScenarioSpec(
        n_sats=4, n_rounds=2, frames_per_pass=2,
        stations=(GroundStation("gs0"),), scene_mix=(SCENE_B,), seed=3))
    pcfg = PipelineConfig(method="targetfuse", score_thresh=0.25)
    run_scenario(space, ground, pcfg, sc, fleet=True, mesh=sats_mesh(4),
                 strict_parity=strict)
    assert not spanning, f"mesh-spanning operands reached {set(spanning)}"


@requires_mesh
def test_sharded_helpers_match_unsharded(counters):
    """prepare_frames_multi / count_tiles_multi / dedup_multi with a
    mesh context are bit-equal to their unsharded outputs."""
    import jax.numpy as jnp
    space, ground = counters
    sh = FleetSharding(sats_mesh(4))
    sp_size = space[1].input_size
    gd_size = ground[1].input_size
    rng = np.random.default_rng(17)
    workloads = []
    for k in (2, 1, 3, 2, 1):
        img, b, c = make_scene(rng, SCENE_A)
        workloads.append(revisit_frames(rng, img, b, c, k))
    multi = prepare_frames_multi(workloads, 128, sp_size, gd_size,
                                 sharding=sh)
    plain = prepare_frames_multi(workloads, 128, sp_size, gd_size)
    for got, want in zip(multi, plain):
        assert got.n == want.n
        np.testing.assert_array_equal(np.asarray(got.tiles_sp)[:got.n],
                                      np.asarray(want.tiles_sp)[:want.n])
        np.testing.assert_array_equal(np.asarray(got.moments)[:got.n],
                                      np.asarray(want.moments)[:want.n])
        np.testing.assert_array_equal(got.roi_std, want.roi_std)

    (params, cfg), _ = counters
    tiles = rng.random((96, cfg.input_size, cfg.input_size, 3)
                       ).astype(np.float32)
    parts = [(tiles, np.arange(0, 96, 3)), (tiles, np.array([5, 2, 77]))]
    for (c1, f1), (c2, f2) in zip(
            count_tiles_multi(params, cfg, parts, score_thresh=0.25,
                              sharding=sh),
            count_tiles_multi(params, cfg, parts, score_thresh=0.25)):
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))

    dparts = [(jnp.asarray(rng.random((128, 9)).astype(np.float32)), 8,
               jax.random.PRNGKey(s), 100 + s) for s in range(5)]
    for got, want in zip(dd.dedup_multi(dparts, sharding=sh),
                         dd.dedup_multi(dparts)):
        for f in got._fields:
            np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                          np.asarray(getattr(want, f)))
