"""The harness's arithmetic and its loader, on the CPU: FLOPs per tile,
roofline shares against the peak table, finding files by name, and the
command refusing to run without a TPU."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

import bench_tiny  # noqa: F401  (puts bench/ and src/ on the path)
from benchlib import check, counters, loader

BENCH, ROOT = bench_tiny.BENCH, bench_tiny.ROOT


@pytest.mark.parametrize("config,role,gflop", [
    ("targetfuse-yolov3", "space", 6.137),
    ("targetfuse-yolov3", "ground", 24.236),
    ("targetfuse-ssd", "space", 0.524),
    ("targetfuse-ssd", "ground", 24.236),
])
def test_forward_gflops(config, role, gflop):
    cfg = loader.config(config)
    spec = cfg["counters"][role]
    assert "arch" not in spec
    assert counters.arch(spec) is loader.arch("plain-grid")
    got = counters.forward_gflops(spec)
    assert got == loader.arch("plain-grid").forward_gflops(spec)
    assert round(got, 3) == gflop
    assert got == pytest.approx(cfg["gflops_per_tile"][role], rel=1e-12)


@pytest.mark.parametrize("config,role", [
    ("targetfuse-yolov3", "space"), ("targetfuse-yolov3", "ground"),
    ("targetfuse-ssd", "space"), ("targetfuse-ssd", "ground"),
])
def test_detector_config_takes_the_entrys_fields(config, role):
    """The driver's ``DetectorConfig`` is the one that naming the plain
    grid's fields one by one gives."""
    from repro.configs.base import DetectorConfig
    spec = loader.config(config)["counters"][role]
    want = DetectorConfig(name=spec["name"], input_size=spec["input_size"],
                          widths=tuple(spec["widths"]),
                          n_blocks_per_stage=spec["n_blocks_per_stage"],
                          n_classes=spec["n_classes"],
                          n_anchors=spec["n_anchors"],
                          param_dtype=spec["param_dtype"])
    assert loader.driver("mission").detector_config(spec) == want


def test_forward_gflops_matches_the_program():
    from repro.configs import get_config
    from repro.core.energy import detector_gflops
    for name in ("targetfuse-space", "targetfuse-ground", "ssd-mobilenetv2"):
        prog = get_config(name)
        spec = dict(input_size=prog.input_size, widths=list(prog.widths),
                    n_blocks_per_stage=prog.n_blocks_per_stage,
                    n_anchors=prog.n_anchors, n_classes=prog.n_classes)
        assert counters.forward_gflops(spec) == detector_gflops(prog)


def test_peaks_and_roofline_arithmetic():
    peaks = loader.peaks("TPU v5 lite")
    assert peaks["bf16_flops"] == 197e12
    assert peaks["int8_ops"] == 393e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    moments = loader.metric("tile_moments_roofline")
    # 256 tiles of 416 px read once: 532 MB at 819 GB/s is 650 us
    least = moments.needed_bytes(256, 416) / peaks["hbm_bytes_per_s"]
    assert least == pytest.approx(256 * (416 * 416 * 12 + 36) / 819e9)

    class Trace:
        def ops_matching(self, pattern, *programs):
            return 2 * least, 1.0
    run = dict(trace=Trace(), tally={"tiles": 256}, peaks=peaks,
               config={"counters": {"space": {"input_size": 416}}})
    assert moments.read(run) == pytest.approx(50.0)
    kmeans = loader.metric("kmeans_assign_roofline")
    b, f = kmeans.call_cost(256, 128)
    assert (b, f) == (4.0 * (256 * 9 + 128 * 9) + 8.0 * 256,
                      2.0 * 256 * 128 * 9 + 3.0 * 256 * 128)
    mfu = loader.metric("counter_mfu")

    class Window:
        window_s = 2.0
    run = dict(trace=Window(), flops=197e12, chips=1, peaks=peaks)
    assert mfu.read(run) == pytest.approx(50.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        loader.peaks("TPU v99")


def test_loader_finds_files_by_name():
    bench = loader.benchmark()
    for w in bench["workloads"]:
        cell = loader.workload(bench, w["name"])
        cfg = loader.config(cell["config"])
        tr = loader.traffic(cell["traffic"])
        assert cfg["name"] == cell["config"] and tr["name"] == cell["traffic"]
        assert hasattr(loader.driver(tr["entry"]), "Driver")
        assert set(loader.limits(w["name"])) == set(check.NUMBERS)
        for m in loader.per_layer_for(bench, w["name"]):
            assert callable(loader.metric(m["name"]).read)
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))


@pytest.mark.parametrize("kind", ["config", "traffic", "metric", "driver",
                                  "limits", "arch"])
def test_loader_refuses_a_missing_name(kind):
    with pytest.raises(loader.MissingFile):
        getattr(loader, kind)("no-such-name")
    with pytest.raises(loader.MissingFile):
        loader.workload(loader.benchmark(), "no-such-cell")


def test_unknown_arch_names_the_missing_file():
    spec = dict(loader.config("targetfuse-yolov3")["counters"]["space"],
                arch="no-such-arch")
    with pytest.raises(loader.MissingFile,
                       match=r"bench/archs/no-such-arch\.py"):
        counters.forward_gflops(spec)


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = loader.benchmark()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(2 ** 40 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_scenes_match_the_program_generator():
    """The benchmark's copy of the scene generator (its noise upsampling
    made separable) draws the scenes the program's generator draws."""
    import numpy as np
    from benchlib import scenes
    from repro.data import synthetic
    spec = loader.traffic("pass-targetfuse")["scenes"]
    ours = scenes.spec_from(spec)
    theirs = synthetic.SceneSpec(ours.name, ours.scene_px,
                                 ours.objects_per_scene, ours.object_px,
                                 ours.n_classes, ours.cloud_fraction,
                                 ours.texture_scale)
    for seed in (0, 1):
        a = scenes.make_scene(np.random.default_rng(seed), ours)
        b = synthetic.make_scene(np.random.default_rng(seed), theirs)
        np.testing.assert_allclose(a[0], b[0], rtol=0, atol=1e-6)
        np.testing.assert_array_equal(a[1], b[1])
        np.testing.assert_array_equal(a[2], b[2])
