"""The reduction from a trace to metrics, on small hand-made events."""
from __future__ import annotations

import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
from benchlib import trace


def test_merge_is_the_union_of_intervals():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (6, 9), (9, 10), (4, 4)]) \
        == [(0, 3), (5, 10)]


def test_gaps_complement_the_busy_union():
    busy = trace.merge([(2, 4), (6, 8)])
    assert trace.gaps(busy, 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert trace.gaps(busy, 3, 7) == [(4, 6)]
    assert trace.gaps([], 0, 5) == [(0, 5)]


def test_idle_goes_to_the_innermost_open_span():
    spans = [("bench.round", 0, 100), ("bench.stage.select", 10, 30),
             ("bench.stage.downlink", 30, 40)]
    got = trace.attribute([(5, 35), (90, 110)], spans)
    assert got == {"bench.round": 5 + 10, "bench.stage.select": 20,
                   "bench.stage.downlink": 5, trace.NO_SPAN: 10}


def _synthetic():
    ops = [("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop", 100, 140),
           # as a v5e trace names a Pallas kernel: after its program, with
           # the tiling inside the layout
           ('%_frame_program_body.2 = f32[4,3,384]{2,1,0:T(4,128)S(1)} '
            'custom-call(%copy.3, %x), custom_call_target="tpu_custom_call"',
            130, 160),
           ("%convolution.7 = f32[8]{0} convolution(f32[8]{0} %y)", 200, 260),
           ("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop", 300, 310)]
    mods = [("jit__frame_program_body(12)", 100, 160),
            ("jit_count_tiles(40)", 200, 260),
            ("jit_count_tiles(40)", 300, 310)]
    spans = [("bench.window", 90, 400), ("bench.round", 90, 250),
             ("bench.stage.capture", 95, 170), ("bench.round", 250, 400),
             ("bench.stage.select", 320, 390)]
    return {"/device:TPU:0": {"ops": ops, "modules": mods}}, spans


def test_reduce_sums_busy_modules_ops_and_idle():
    devices, spans = _synthetic()
    s = trace.reduce(devices, spans)
    assert (s.t0, s.t1) == (90, 400)
    assert s.busy_ns == 60 + 60 + 10
    assert s.module_ns == {"jit__frame_program_body": 60,
                           "jit_count_tiles": 70}
    assert s.module_s("count_tiles") == 70e-9
    assert s.ops_matching("custom.call", "_frame_program_body") == (30e-9, 1)
    assert s.ops_matching("custom.call", "count_tiles") == (0.0, 0)
    assert s.ops_matching("fusion") == (50e-9, 2)
    assert s.op_count[("jit_count_tiles", "%fusion.1 = f32[4] fusion")] == 1
    assert s.top_ops(1) == [["jit_count_tiles: %convolution.7 = f32[8] "
                             "convolution", 60e-9]]
    idle = s.idle_by_span
    assert sum(idle.values()) == (400 - 90) - 130
    assert idle["bench.stage.capture"] == 5 + 10
    assert idle["bench.stage.select"] == 70
    assert idle["bench.round"] == 5 + 30 + 40 + 10 + 10


@pytest.mark.parametrize("name,label", [
    ("%fusion.87 = f32[64,416,416,32]{0,3,2,1:T(8,128)} fusion(f32[64]{0} "
     "%p), kind=kLoop", "%fusion.87 = f32[64,416,416,32] fusion"),
    ('%_dedup_core_body.1 = (s32[1,256]{1,0:T(1,128)}, f32[1,256]{1,0:T(1,'
     '128)}) custom-call(%bitcast.5, %q.1), custom_call_target="tpu_custom_'
     'call", frontend_attributes={kernel_metadata={}}',
     "%_dedup_core_body.1 = (s32[1,256], f32[1,256]) custom-call"),
    ("fusion.3", "fusion.3"),
])
def test_op_label_keeps_name_shape_and_opcode(name, label):
    assert trace.op_label(name) == label


def test_reduce_averages_over_chips():
    devices, spans = _synthetic()
    devices["/device:TPU:1"] = {"ops": [("%fusion.1 = f32[4]{0} fusion(",
                                         100, 110)], "modules": []}
    s = trace.reduce(devices, spans)
    assert s.n_devices == 2
    assert s.busy_ns == (130 + 10) / 2


def test_reduce_needs_the_window_span():
    devices, spans = _synthetic()
    with pytest.raises(ValueError):
        trace.reduce(devices, [sp for sp in spans if sp[0] != "bench.window"])


def test_align_moves_host_spans_onto_the_trace_clock():
    # the trace counts from its own start; the host clock is 10**9 ahead,
    # and the host saw each marker end 3 or 5 ns late
    mark = "jit_bench_clock_mark(7)"
    devices = {"/device:TPU:0": {"ops": [], "modules": [
        (mark, 10, 20), ("jit_count_tiles(3)", 30, 90), (mark, 100, 110)]}}
    spans = [("bench.window", 10 ** 9 + 25, 10 ** 9 + 95)]
    got, skew = trace.align(spans, [10 ** 9 + 23, 10 ** 9 + 115], devices)
    assert got == [("bench.window", 22, 92)] and skew == 2
    with pytest.raises(ValueError):
        trace.align(spans, [10 ** 9 + 23], devices)


def test_spans_record_only_while_on():
    rec = trace.Spans()
    with rec("bench.round"):
        pass
    assert rec.events == []
    rec.on = True
    with rec("bench.round"):
        with rec("bench.stage.capture"):
            pass
    (inner, i0, i1), (outer, o0, o1) = rec.events
    assert (inner, outer) == ("bench.stage.capture", "bench.round")
    assert o0 <= i0 <= i1 <= o1


def test_traced_window_keeps_the_benchmark_spans(tmp_path):
    import bench_tiny
    bench, cell, config, traffic = bench_tiny.tiny_cell()
    run = bench_tiny.run_module()
    ctx = run.Ctx(cell, config, traffic, bench_tiny.SEED, 1)
    driver = bench_tiny.loader.driver(traffic["entry"]).Driver(ctx)
    driver.setup()
    lat, spans, marks = run.traced_window(driver, 2, str(tmp_path))
    names = [sp[0] for sp in spans]
    assert len(lat) == 2 and names.count("bench.round") == 2
    assert names.count("bench.window") == 1
    assert "bench.stage.capture" in names and "bench.stage.aggregate" in names
    assert not trace.SPANS.on
    win = next(sp for sp in spans if sp[0] == "bench.window")
    assert all(win[1] <= s <= e <= win[2] for _, s, e in spans)
    assert len(marks) == 2 and marks[0] <= win[1] and win[2] <= marks[1]
