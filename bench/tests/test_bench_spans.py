"""The readings of the program's own spans and counters, on small
hand-made events, and the traced window with the program's recorder on
(tiny cell, CPU)."""
from __future__ import annotations

import importlib.util
import os

import pytest

import bench_tiny
from benchlib import program_spans as ps
from benchlib import trace


def _events():
    """One chip; host spans of one round (already on the trace's clock)."""
    mods = [("jit__frame_program_body(3)", 20, 30),
            ("jit_gather(5)", 42, 46),
            ("jit_count_tiles(9)", 48, 70),      # space tier
            ("jit_gather(5)", 82, 84),
            ("jit_concatenate(6)", 85, 86),
            ("jit_count_tiles(9)", 86, 110),     # ground tier
            ("jit_count_tiles(9)", 150, 160),    # outside every span
            ("jit_count_tiles(9)", 195, 230)]    # crosses the window's end
    spans = [("bench.window", 0, 200),
             ("mission.ingest", 5, 75), ("stage.capture", 6, 35),
             ("capture.fill", 7, 15), ("capture.to_device", 15, 19),
             ("capture.program", 19, 20),
             ("stage.onboard_count", 40, 74), ("count.space", 41, 73),
             ("count.gather", 41, 47), ("count.program", 47, 49),
             ("mission.contact", 78, 120), ("stage.ground_recount", 80, 118),
             ("count.ground", 81, 117), ("count.pad", 84, 86),
             ("capture.fill", 190, 199)]
    return {"/device:TPU:0": {"ops": [], "modules": mods}}, spans


def test_module_time_goes_to_every_enclosing_span():
    devices, spans = _events()
    by = ps.module_ns_by_span(devices, spans, 0, 200)
    # the space tier's count program started inside count.program, which
    # nests in count.space, stage.onboard_count and mission.ingest
    for sp in ("count.program", "count.space", "stage.onboard_count",
               "mission.ingest"):
        assert by[(sp, "jit_count_tiles")] == 22
    assert by[("count.ground", "jit_count_tiles")] == 24
    assert by[("count.pad", "jit_concatenate")] == 1
    assert by[("count.ground", "jit_gather")] == 2
    # a program starting outside every span goes to none but the window;
    # only its in-window time counts
    assert by[("bench.window", "jit_count_tiles")] == 22 + 24 + 10 + 5
    assert not any(sp == "count.space" and v == 10 for (sp, _), v in
                   by.items())
    # started after the capture spans closed: the stage only
    assert ("capture.program", "jit__frame_program_body") not in by
    assert by[("stage.capture", "jit__frame_program_body")] == 10


def test_module_time_is_averaged_over_chips():
    devices, spans = _events()
    devices["/device:TPU:1"] = {"ops": [], "modules": [
        ("jit_count_tiles(9)", 50, 54)]}
    by = ps.module_ns_by_span(devices, spans, 0, 200)
    assert by[("count.space", "jit_count_tiles")] == (22 + 4) / 2


def test_a_span_name_is_credited_once_when_nested_in_itself():
    devices = {"/device:TPU:0": {"ops": [], "modules": [
        ("jit_count_tiles(1)", 5, 9)]}}
    spans = [("stage.x", 0, 20), ("stage.x", 2, 10)]
    assert ps.module_ns_by_span(devices, spans, 0, 20) == {
        ("stage.x", "jit_count_tiles"): 4}


def _run(counters=None, with_spans=True):
    devices, spans = _events()
    if not with_spans:
        spans = [sp for sp in spans if sp[0].startswith("bench.")]
    summ = trace.reduce(devices, spans)
    run = {"trace": summ, "tally": {"rounds": 2}, "counters": counters}
    if with_spans:
        run["module_ns_by_span"] = ps.module_ns_by_span(
            devices, summ.spans, summ.t0, summ.t1)
        run["devices"] = devices
    return run


def test_host_fill_reader_sums_span_durations_per_round():
    run = _run()
    # two capture.fill spans: 8 ns and 9 ns, in two rounds
    assert ps.capture_host_fill_ms_per_round(run) == pytest.approx(
        (8 + 9) / 1e6 / 2)


def test_h2d_reader_reads_the_copy_in_flight_not_the_host_call():
    run = _run()
    # capture.to_device runs 15-19 on the host; the frame program that
    # waited for the copy starts at 20
    assert ps.capture_h2d_ms_per_round(run) == pytest.approx(5 / 1e6 / 2)


def test_device_readers_split_count_by_tier():
    run = _run()
    assert ps.count_ground_device_ms_per_round(run) == pytest.approx(
        24 / 1e6 / 2)
    assert ps.count_space_device_ms_per_round(run) == pytest.approx(
        22 / 1e6 / 2)
    # gathers (4 + 2) and the pad concatenation (1)
    assert ps.count_copy_device_ms_per_round(run) == pytest.approx(
        7 / 1e6 / 2)


def test_pad_share_reads_the_counter_deltas():
    run = _run({"count.rows_real": 93, "count.rows_computed": 128})
    assert ps.count_pad_share(run) == pytest.approx(100 * 35 / 128)


@pytest.mark.parametrize("name", sorted(ps.METRICS))
def test_each_reader_returns_none_without_its_input(name):
    _, read = ps.METRICS[name]
    assert read(_run(counters={}, with_spans=False)) is None
    assert read({**_run(with_spans=False), "counters": None}) is None


def test_idle_gaps_name_program_spans():
    devices, spans = _events()
    summ = trace.reduce(devices, spans)
    idle = summ.idle_by_span
    # no device op ran (the events carry modules only): all of the
    # window is idle, each part on the innermost span open
    assert sum(idle.values()) == 200
    assert idle["capture.fill"] == 8 + 9
    assert idle["count.space"] == (73 - 41) - (47 - 41) - (49 - 47)


def _spans_module():
    spec = importlib.util.spec_from_file_location(
        "bench_spans", os.path.join(bench_tiny.BENCH, "spans.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_capture_idle_share_of_the_stage():
    devices, spans = _events()
    bench_only = [("bench.window", 0, 200), ("bench.stage.capture", 6, 35)]
    summ_b = trace.reduce(devices, bench_only)
    summ_p = trace.reduce(devices, bench_only + spans[1:-1])
    got = _spans_module().capture_idle(summ_b, summ_p)
    # no device op ran: the whole stage is idle, and the program's fill,
    # copy and dispatch spans name 13 of its 29 ns
    assert got["stage_idle_s"] == (35 - 6) / 1e9
    assert got["capture_spans_idle_s"] == (8 + 4 + 1) / 1e9
    assert got["share"] == pytest.approx(13 / 29)


def test_h2d_in_flight_runs_from_the_copy_to_the_program():
    devices = {"/device:TPU:0": {"ops": [], "modules": [
        ("jit__frame_program_body(3)", 30, 40),
        ("jit_count_tiles(9)", 50, 60),
        ("jit__frame_program_body(3)", 120, 130)]}}
    spans = [("capture.to_device", 10, 12), ("capture.to_device", 100, 101),
             ("capture.to_device", 190, 191)]
    # the last copy has no frame program after it inside the window
    assert ps.h2d_in_flight_ns(devices, spans, 0, 200) == (
        (30 - 10) + (120 - 100))


def test_h2d_in_flight_is_averaged_over_chips():
    devices = {"/device:TPU:0": {"ops": [], "modules": [
        ("jit__frame_program_multi(3)", 30, 40)]},
               "/device:TPU:1": {"ops": [], "modules": [
        ("jit__frame_program_multi(3)", 20, 40)]}}
    spans = [("capture.to_device", 10, 12)]
    assert ps.h2d_in_flight_ns(devices, spans, 0, 200) == (20 + 10) / 2


def test_recorded_window_keeps_program_spans_and_counters(tmp_path):
    from repro.core import obs
    bench, cell, config, traffic = bench_tiny.tiny_cell()
    run = bench_tiny.run_module()
    ctx = run.Ctx(cell, config, traffic, bench_tiny.SEED, 1)
    driver = bench_tiny.loader.driver(traffic["entry"]).Driver(ctx)
    driver.setup()
    mod = _spans_module()
    lat, spans, marks, prog, counters, n_rec = mod.recorded_window(
        run, driver, 2, str(tmp_path))
    assert not obs.enabled() and not trace.SPANS.on
    names = [sp[0] for sp in prog]
    assert n_rec >= len(prog) > 0
    for sp in ("mission.ingest", "mission.contact", "stage.capture",
               "capture.fill", "capture.to_device", "count.space"):
        assert sp in names
    win = next(sp for sp in spans if sp[0] == "bench.window")
    assert all(win[1] <= s <= e <= win[2] for _, s, e in prog)
    assert counters["capture.frames_real"] == driver.tally["frames"]
    assert counters["count.rows_computed"] >= counters["count.rows_real"] > 0
    # the recorder-cost blocks that follow: off stays off, on turns off
    assert len(mod.run_rounds(driver, 2, record=False)) == 2
    assert not obs.enabled()
    lat = mod.run_rounds(driver, 2, record=True)
    assert len(lat) == 2 and not obs.enabled()
    assert any(r.name == "capture.fill" for r in obs.records())
