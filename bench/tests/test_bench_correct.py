"""The comparison that decides ``correct``, driven through the harness on
the CPU at a tiny size: a sound run passes; the control (the reference
one precision step below the configuration's, in the program's place)
and each fault a one-chip mission cell can have fail."""
from __future__ import annotations

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import bench_tiny  # noqa: E402


def test_sound_run_is_correct():
    out = bench_tiny.execute()
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"tiles_per_s", "round_p95_ms", "setup_s"}


def test_counts_are_not_trivial():
    """The tiny counters count boxes on some tiles and none on others, so
    a wrong count has something to differ from."""
    from benchlib import loader
    from benchlib.reference import Reference
    bench, cell, config, traffic = bench_tiny.tiny_cell()
    ctx = bench_tiny.run_module().Ctx(cell, config, traffic,
                                      bench_tiny.SEED, 1)
    drv = loader.driver("mission").Driver(ctx)
    drv.setup()
    ref = Reference(drv.counters, config, traffic, mode="highest")
    for role in ("space", "ground"):
        kept, counts = [], []
        for key, frames in enumerate(drv.pool):
            tiles, _ = ref.capture(key, frames)
            idx = range(len(tiles))
            kept += ref.kept(key, tiles, role, idx)
            counts += list(ref.counts(key, tiles, role, idx)[0])
        counts = np.array(counts)
        assert counts.max() > 0 and (counts == 0).any(), role


def _control_verdict(mode):
    """The control ``mode`` in the program's place, judged against the
    reference at the configuration's precision (float32 on the CPU)."""
    from benchlib import check, loader
    from benchlib.reference import Reference
    bench, cell, config, traffic = bench_tiny.tiny_cell()
    ctx = bench_tiny.run_module().Ctx(cell, config, traffic, 11, 1)
    drv = loader.driver("mission").Driver(ctx)
    drv.setup()
    keys = list(range(len(drv.pool)))
    passes = dict(enumerate(drv.pool))
    ctrl = Reference(drv.counters, config, traffic, mode=mode)
    rounds, summary = ctrl.session([passes[k] for k in keys], keys)
    ref = Reference(drv.counters, config, traffic, mode="highest")
    numbers = check.compare([dict(keys=keys, rounds=rounds,
                                  summary=summary)], ref, passes)
    return check.verdict(numbers, bench_tiny.tiny_limits())


def test_control_fails():
    """The reference one precision step down, in the program's place."""
    ok, rows = _control_verdict("control")
    assert not ok, rows


@pytest.mark.parametrize("mode", ["control-capture", "control-count"])
def test_stage_control_fails(mode):
    """Each stage's control alone (capture and dedup in bfloat16, or the
    counters' convs in int8) fails the comparison too."""
    ok, rows = _control_verdict(mode)
    assert not ok, rows


def _count_tiles_patched(monkeypatch, alter):
    import repro.core.cascade as cascade
    real = cascade.count_tiles

    def patched(params, cfg, tiles, *a, **k):
        c, f = real(params, cfg, tiles, *a, **k)
        return alter(c, f)

    monkeypatch.setattr(cascade, "count_tiles", patched)


def test_fault_step_returns_state_unchanged(monkeypatch):
    from repro.core import mission
    monkeypatch.setattr(mission.OnboardCount, "run",
                        lambda self, m, seg, window=None: None)
    assert not bench_tiny.execute()["correct"]


def test_fault_half_batch_left_out(monkeypatch):
    def half(c, f):
        keep = np.arange(c.shape[0]) < c.shape[0] // 2
        return c * keep, f * keep
    _count_tiles_patched(monkeypatch, half)
    assert not bench_tiny.execute()["correct"]


def test_fault_answer_altered_where_produced(monkeypatch):
    _count_tiles_patched(monkeypatch, lambda c, f: (c + 1.0, f))
    assert not bench_tiny.execute()["correct"]


@pytest.mark.parametrize("value", [np.nan, np.inf])
def test_non_finite_number_fails(value):
    from benchlib import check
    limits = bench_tiny.tiny_limits()
    numbers = dict.fromkeys(check.NUMBERS, 0.0)
    numbers["moments_gap"] = value
    assert not check.verdict(numbers, limits)[0]
