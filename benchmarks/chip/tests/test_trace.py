"""The trace reduction, on hand-made event lists and on a small trace
recorded on the chip (``recorded_trace.json``).  No chip needed:

    python3 -m pytest benchmarks/chip/tests/test_trace.py
"""
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import roofline  # noqa: E402
import tracing  # noqa: E402
from metrics import kernels  # noqa: E402

MS = 1_000_000


def _trace():
    # window: the two step spans, 0..10 ms; device busy 1..3, 2..4
    # (overlapping), 6..7 ms and 9..12 ms (cut at the window's end)
    spans = [["bench.step", 0, 5 * MS], ["bench.step", 5 * MS, 5 * MS],
             ["bench.fetch", 4 * MS, 1 * MS], ["bench.dispatch", 1 * MS, 1],
             ["bench.dispatch", 6 * MS, 1]]
    ops = [["event_voxel_op.1", 1 * MS, 2 * MS], ["fusion.1", 2 * MS, 2 * MS],
           ["_spike_conv_impl.3", 6 * MS, 1 * MS], ["copy", 9 * MS, 3 * MS]]
    modules = [["jit__step", 1 * MS, 3 * MS], ["jit__step", 6 * MS, 6 * MS],
               ["jit_other", 6 * MS, 1 * MS]]
    return tracing.Trace(ops=ops, modules=modules, spans=spans)


def test_busy_union_and_window():
    tr = _trace()
    assert tr.window_s == pytest.approx(10e-3)
    assert tr.busy == [[1 * MS, 4 * MS], [6 * MS, 7 * MS], [9 * MS, 10 * MS]]
    assert tr.busy_s == pytest.approx(5e-3)


def test_idle_gaps_labelled_by_innermost_span():
    gaps = dict()
    for label, sec in _trace().idle_gaps():
        gaps[label] = gaps.get(label, 0.0) + sec
    # 0..1 and 4..5 (the fetch span is innermost there) and 5..6, 7..9
    assert gaps["bench.step"] == pytest.approx(4e-3)
    assert gaps["bench.fetch"] == pytest.approx(1e-3)


def test_kernel_time_and_ticks():
    tr = _trace()
    assert tr.kernel_ns(kernels.VOXEL) == (2 * MS, 1)
    assert tr.kernel_ns(kernels.CONV) == (1 * MS, 1)
    assert len(tr.ticks()) == 2
    bd = tr.breakdown()
    assert bd["device_ops"][0] == ["copy", pytest.approx(3e-3)]
    assert len(bd["idle_gaps"]) <= tracing.TOP


def test_metric_readers_on_hand_made_trace():
    from metrics import device_idle_share, encode_ms, step_host_ms
    ctx = types.SimpleNamespace(trace=_trace(), notes=[])
    assert device_idle_share.read(ctx) == pytest.approx(50.0)
    assert encode_ms.read(ctx) == pytest.approx(1.0)     # 2 ms / 2 ticks
    # 10 ms of steps, 1 ms of it in fetch, over 2 dispatched ticks
    assert step_host_ms.read(ctx) == pytest.approx(4.5)


def test_late_share_counts_late_and_never_delivered():
    from harness import Record
    from metrics import late_share
    recs = [Record(0, 0, t_ref=0.0, t_deliver=0.010, status="done"),
            Record(1, 0, t_ref=0.0, t_deliver=0.060, status="done"),
            Record(2, 0, t_ref=0.1, status="rejected"),
            Record(3, 0, t_ref=0.2, t_deliver=0.220, status="done"),
            Record(4, 0, t_ref=9.0, t_deliver=9.5, status="done")]
    cell = types.SimpleNamespace(open_loop=True,
                                 config={"assumed": {"window_hz": 20}})
    ctx = types.SimpleNamespace(cell=cell, records=recs, untraced_until=1.0)
    assert late_share.read(ctx) == pytest.approx(50.0)   # 2 of the 4 due
    cell.open_loop = False
    assert late_share.read(ctx) is None


def test_flop_count_of_the_registered_yolo():
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "yolo64.json")) as f:
        cfg = json.load(f)
    calls = roofline.conv_calls(cfg, batch=1)
    per_step = sum(f for _, f, _ in calls) / 2 / cfg["time_steps"]
    # 62 M multiply-adds per sample and time step, the head's included
    assert 61e6 < per_step < 63e6
    with pytest.raises(KeyError):
        roofline.peaks("TPU v0 imaginary")


def test_recorded_chip_trace():
    path = os.path.join(HERE, "recorded_trace.json")
    with open(path) as f:
        rec = json.load(f)
    tr = tracing.Trace(ops=rec["ops"], modules=rec["modules"],
                       spans=rec["spans"])
    assert 0 < tr.busy_s <= tr.window_s
    ticks = tr.ticks()
    assert ticks
    for pattern, n in rec["expected_calls_per_tick"].items():
        assert tr.kernel_ns(getattr(kernels, pattern))[1] == n * len(ticks)
    assert tr.busy_s == pytest.approx(rec["expected_busy_s"], rel=1e-9)
