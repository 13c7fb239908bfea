"""The reduction from a profiler trace to device busy, idle and per-study
time, on hand-made traces."""
import os

import pytest

from chipbench import harness, spec, tracing

HERE = os.path.dirname(os.path.abspath(__file__))

# reader of each per-layer metric, found as the harness finds it
READ = {name: spec._reader(HERE, name)
        for name in ("study_device_ms", "device_idle_pct",
                     "chip_busy_spread_pct")}


def test_merge_covered_gaps():
    merged = tracing.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [(0, 3), (5, 8)]
    assert tracing.covered(merged, 2, 6) == 2      # [2,3] + [5,6]
    assert tracing.gaps(merged, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert tracing.gaps(merged, 1, 2) == []


def two_chip_trace():
    """Two studies of 100 ns each, on two chips, with host pieces."""
    spans = [("study", 0, 100), ("prepare", 0, 10), ("dispatch", 10, 20),
             ("wait", 20, 90), ("fetch", 90, 100),
             ("study", 120, 220), ("prepare", 120, 130),
             ("dispatch", 130, 140), ("wait", 140, 210),
             ("fetch", 210, 220)]
    busy = [[(20, 90), (140, 210)],           # chip 0: 70 + 70
            [(20, 55), (140, 175)]]           # chip 1: 35 + 35
    ops = [{"fusion.1 (fc.1)": 100.0, "copy.2": 40.0},
           {"fusion.1 (fc.1)": 50.0, "copy.2": 20.0}]
    return tracing.Trace(["/device:TPU:0", "/device:TPU:1"], busy, ops,
                         spans)


def test_trace_reduction_two_chips():
    tr = two_chip_trace()
    assert tr.window() == (0, 220)
    assert tr.busy_s() == pytest.approx([140e-9, 70e-9])
    assert [row == pytest.approx([70e-9, 35e-9])
            for row in tr.study_busy_s()] == [True, True]
    assert tr.top_ops(1) == [["fusion.1 (fc.1)", pytest.approx(75e-9)]]
    gaps = dict((k, v) for k, v in tr.idle_by_host())
    # chip 0 idles in prepare+dispatch (20+20) and fetch (10+10) and
    # between the studies (20); chip 1 also in the tail of each wait
    assert gaps["between studies"] == pytest.approx(20e-9)
    assert gaps["wait"] == pytest.approx((35 + 35) / 2 * 1e-9)
    assert gaps["fetch"] == pytest.approx(20e-9)
    assert sum(gaps.values()) == pytest.approx(220e-9 - (140e-9 + 70e-9) / 2)


def record_of(trace):
    return harness.Record(chips=len(trace.chips) if trace else 1, setup_s=1.0, build_s=0.1,
                          compile_s=0.2, cache_hits=0, studies=[],
                          memory_peak_bytes=0, trace=trace)


def test_per_layer_readers_on_two_chips():
    rec = record_of(two_chip_trace())
    assert READ["study_device_ms"](rec) == pytest.approx(70e-9 * 1e3)
    assert READ["device_idle_pct"](rec) == pytest.approx(
        100 * ((1 - 140 / 220) + (1 - 70 / 220)) / 2)


def test_chip_busy_spread_on_two_chips():
    """Chip 1 runs 35 ns of chip 0's 70 in each study: a spread of 50%;
    on one chip there is nothing to read."""
    tr = two_chip_trace()
    assert READ["chip_busy_spread_pct"](record_of(tr)) == pytest.approx(50.0)
    tr.busy[1] = [(20, 90), (140, 175)]      # 70 and 35 ns: 0% and 50%
    assert READ["chip_busy_spread_pct"](record_of(tr)) == pytest.approx(25.0)
    one = tracing.Trace(tr.chips[:1], tr.busy[:1], tr.op_ns[:1], tr.spans)
    assert READ["chip_busy_spread_pct"](record_of(one)) is None


def test_per_layer_readers_find_nothing_without_a_trace():
    rec = record_of(None)
    assert all(read(rec) is None for read in READ.values())


def test_short_op_names():
    name = ("%fusion.458 = (s32[32]{0}, s32[32]{0}) fusion(s32[64,32]{1,0} "
            "%get-tuple-element.4289), kind=kLoop, "
            "calls=%fused_computation.75.clone")
    assert tracing.short_op(name) == "fusion.458 (fused_computation.75.clone)"
    assert tracing.short_op("%copy.3 = f32[8]{0} copy(f32[8]{0} %p)") \
        == "copy.3"



def test_extract_keeps_only_the_cells_chips():
    """A one-chip cell traced where more chips are visible: the idle
    chips' planes are left out, so they do not dilute the busy share."""
    from types import SimpleNamespace as NS
    ev = lambda name, start, dur: NS(name=name, start_ns=start,
                                     duration_ns=dur)
    line = lambda name, *events: NS(name=name, events=list(events))
    busy = NS(name="/device:TPU:0", lines=[
        line("XLA Modules", ev("jit_fn", 10, 80)),
        line("XLA Ops", ev("%fusion.1 = f32[8] fusion(), calls=%fc.1", 10, 80))])
    idle = NS(name="/device:TPU:1", lines=[line("XLA Modules")])
    host = NS(name="/host:CPU", lines=[line(
        "python", ev("study", 0, 100), ev("wait", 5, 90), ev("other", 0, 1))])
    tr = tracing.extract(NS(planes=[idle, host, busy]), [0])
    assert tr.chips == ["/device:TPU:0"]
    assert tr.busy_s() == pytest.approx([80e-9])
    assert READ["device_idle_pct"](record_of(tr)) == pytest.approx(20.0)
    assert tr.top_ops() == [["fusion.1 (fc.1)", pytest.approx(80e-9)]]
    assert sorted(n for n, _, _ in tr.spans) == ["study", "wait"]
    both = tracing.extract(NS(planes=[idle, host, busy]), [0, 1])
    assert READ["device_idle_pct"](record_of(both)) == pytest.approx(60.0)
