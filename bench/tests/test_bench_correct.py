"""The comparison that decides ``correct``, driven through the harness on
the CPU at a tiny size: a sound run passes; the control (the reference
one precision step below the configuration's, in the program's place)
and each fault a one-chip mission cell can have fail; a counter
architecture that a configuration brings goes through it unedited."""
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


class ToyTwoGrid:
    """A counter architecture of two grids, as ``bench/archs/<arch>.py``
    would give it: a stride-2 3x3 conv with a 1x1 head on its grid, then
    another stride-2 3x3 conv with a 1x1 head on the coarser grid; decode
    puts one-cell boxes at sigmoid offsets in each grid."""

    @staticmethod
    def shapes(spec):
        c, hw = spec["widths"][0], spec["n_anchors"] * (5 + spec["n_classes"])
        return {"trunk": (3, 3, 3, c), "fine": (1, 1, c, hw),
                "down": (3, 3, c, c), "coarse": (1, 1, c, hw)}

    @staticmethod
    def forward_gflops(spec):
        grid = {"trunk": 2, "fine": 2, "down": 4, "coarse": 4}
        return sum(2 * (-(-spec["input_size"] // grid[n])) ** 2
                   * float(np.prod(s))
                   for n, s in ToyTwoGrid.shapes(spec).items()) / 1e9

    @staticmethod
    def init_params(key, spec):
        import jax
        shapes = ToyTwoGrid.shapes(spec)
        return {n: jax.random.normal(k, s) / np.sqrt(np.prod(s[:3]))
                for k, (n, s) in zip(jax.random.split(key, len(shapes)),
                                     shapes.items())}

    @staticmethod
    def calibrate_head(params, spec, tiles):
        return params

    @staticmethod
    def reference_forward(params, spec, tiles, mode):
        import jax.numpy as jnp
        from benchlib.counters import conv
        fine = jnp.maximum(conv(tiles, params["trunk"], 2, mode), 0.0)
        coarse = jnp.maximum(conv(fine, params["down"], 2, mode), 0.0)
        out = {}
        for name, x in (("fine", fine), ("coarse", coarse)):
            h = conv(x, params[name], 1, mode)
            out[name] = h.reshape(*h.shape[:3], spec["n_anchors"], -1)
        return out

    @staticmethod
    def decode(raw, spec):
        boxes, scores = [], []
        for name in ("fine", "coarse"):
            r = np.asarray(raw[name], np.float64)
            b, g = r.shape[:2]
            cell = spec["input_size"] / g
            sig = 1.0 / (1.0 + np.exp(-r))
            centre = (np.arange(g) + 0.5) * cell
            cx = centre[None, None, :, None] + (sig[..., 0] - 0.5) * cell
            cy = centre[None, :, None, None] + (sig[..., 1] - 0.5) * cell
            box = np.stack([cx - cell / 2, cy - cell / 2,
                            cx + cell / 2, cy + cell / 2], -1)
            boxes.append(box.reshape(b, -1, 4))
            scores.append((sig[..., 4] * sig[..., 5:].max(-1)).reshape(b, -1))
        return np.concatenate(boxes, 1), np.concatenate(scores, 1)


def test_an_arch_file_is_all_a_new_counter_needs(monkeypatch):
    """A configuration that names an architecture of its own gets its
    weights, FLOPs and reference from that file: the driver's FLOPs and
    the reference's kept scores follow it with no edit to the harness."""
    import jax
    import jax.numpy as jnp
    from benchlib import counters, loader
    from benchlib.reference import Reference
    real = loader.arch
    monkeypatch.setattr(loader, "arch", lambda name: ToyTwoGrid
                        if name == "toy-two-grid" else real(name))
    bench, cell, config, traffic = bench_tiny.tiny_cell()
    roles = ("space", "ground")
    for role in roles:
        config["counters"][role]["arch"] = "toy-two-grid"
    ctx = bench_tiny.run_module().Ctx(cell, config, traffic,
                                      bench_tiny.SEED, 1)
    drv = loader.driver("mission").Driver(ctx)
    drv.setup()
    gflops = {r: ToyTwoGrid.forward_gflops(config["counters"][r])
              for r in roles}
    drv.tally.update(space_tiles=3, ground_tiles=5)
    assert drv.flops() == 1e9 * (3 * gflops["space"] + 5 * gflops["ground"])
    ref = Reference(drv.counters, config, traffic, mode="highest")
    assert ref.gflops_space == gflops["space"]
    tiles, _ = ref.capture(0, drv.pool[0])
    idx = np.arange(0, len(tiles), 3)[:20]  # a batch and a part of one
    for role in roles:
        params, spec = drv.counters[role]
        got = ref.kept(0, tiles, role, idx)
        x = counters.resize(jnp.asarray(tiles[idx]), spec["input_size"])
        raw = jax.tree.map(np.asarray, ToyTwoGrid.reference_forward(
            params, spec, x, "highest"))
        boxes, scores = ToyTwoGrid.decode(raw, spec)
        g = spec["input_size"] // 2
        assert scores.shape == (len(idx), (g * g + g * g // 4)
                                * spec["n_anchors"])
        for j in range(len(idx)):
            want = counters.kept_scores(boxes[j], scores[j],
                                        config["nms_iou"])
            assert len(want) > 1
            np.testing.assert_allclose(got[j], want, rtol=1e-6, atol=0)


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
