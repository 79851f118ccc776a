"""The reduction of the program's own spans and stage scopes
(``stages.py`` and the readers that use it), on hand-made event lists
and on a small trace recorded on the chip
(``recorded_trace_serve.json``).  No chip needed:

    python3 -m pytest benchmarks/chip/tests/test_stages.py
"""
import json
import os
import re
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import stages  # noqa: E402
import tracing  # noqa: E402
from metrics import (batch_fill, deliver_ms, kernels, npu_glue_ms,  # noqa
                     pack_ms, step_host_ms)

MS = 1_000_000
READERS = (pack_ms, batch_fill, deliver_ms, npu_glue_ms)


def _trace(with_program=True):
    # two steps, 0..5 and 5..10 ms, each wrapped by the benchmark's span;
    # the program's spans nest inside, its upload and dispatch inside
    # the benchmark's wrappers of them, its fetch span around the
    # wrapper of fetch
    spans = [["bench.step", 0, 5 * MS], ["bench.step", 5 * MS, 5 * MS],
             ["bench.upload", 1 * MS, MS], ["bench.dispatch", 2 * MS, MS],
             ["bench.upload", 6 * MS, MS], ["bench.dispatch", 7 * MS, MS],
             ["bench.fetch", 8 * MS + MS // 10, MS * 8 // 10]]
    serve = [["serve.step", 0, 5 * MS, {"tick": 3}],
             ["serve.shed", 0, MS // 10, {"tick": 3}],
             ["serve.pack", MS // 2, MS // 2, {"tick": 3, "slots": 8,
                                               "queued": 4}],
             ["serve.upload", 1 * MS, MS, {"tick": 3, "bytes": 100}],
             ["serve.dispatch", 2 * MS, MS, {"tick": 3}],
             ["serve.step", 5 * MS, 5 * MS, {"tick": 4}],
             ["serve.pack", 5 * MS, MS, {"tick": 4, "slots": 6,
                                         "queued": 0}],
             ["serve.upload", 6 * MS, MS, {"tick": 4, "bytes": 100}],
             ["serve.dispatch", 7 * MS, MS, {"tick": 4}],
             ["serve.fetch", 8 * MS, MS, {"tick": 3, "bytes": 50}],
             ["serve.deliver", 9 * MS, MS // 2, {"tick": 3, "slots": 8}]]
    # device: the tick's stages in turn; an unscoped copy between two
    # npu ops is the npu's, one between npu and isp keeps no stage
    ops = [["event_voxel_op.1", 3 * MS, MS // 2, "encode"],
           ["_spike_conv_impl.3", 3 * MS + MS // 2, MS // 2, "npu"],
           ["fusion.7", 4 * MS, MS // 2, ""],
           ["pad.2", 4 * MS + MS // 2, MS // 2, "npu"],
           ["copy.9", 5 * MS, MS // 4, ""],
           ["vmap_jit_stencil_segment_op__.1", 5 * MS + MS // 4, MS,
            "isp"]]
    modules = [["jit__step", 3 * MS, 3 * MS + MS // 4]]
    tr = tracing.Trace(ops=[o[:3] for o in ops], modules=modules,
                       spans=spans)
    if with_program:
        tr.serve_spans, tr.op_stages = serve, ops
    return tr


def _ctx(tr):
    return types.SimpleNamespace(trace=tr, notes=[], batch=8)


def test_unscoped_op_takes_its_neighbours_stage_where_they_agree():
    got = {o[0]: (o[3], o[4]) for o in stages.staged(_trace().op_stages)}
    assert got["fusion.7"] == ("npu", True)
    assert got["copy.9"] == ("", False)          # npu before, isp after
    assert got["pad.2"] == ("npu", False)
    assert stages.stage_of("jit(_step)/npu/jit(_pad)/pad:") == "npu"
    assert stages.stage_of("jit(_step)/jit(other)/add:") == ""


def test_readers_on_hand_made_lists():
    ctx = _ctx(_trace())
    # 0.5 + 1 ms of packing over 2 dispatched ticks
    assert pack_ms.read(ctx) == pytest.approx(0.75)
    assert batch_fill.read(ctx) == pytest.approx(100.0 * 14 / 16)
    assert deliver_ms.read(ctx) == pytest.approx(0.5)
    # npu stage: conv 0.5 (a kernel), fusion 0.5 and pad 0.5 (glue)
    assert npu_glue_ms.read(ctx) == pytest.approx(1.0)
    notes = " ".join(ctx.notes)
    assert "no stage 7.692" in notes            # 0.25 of 3.25 ms
    # the program's view of the step outside its fetch, against the
    # benchmark's wrappers (whose fetch is 0.2 ms shorter)
    assert stages.host_ms(ctx.trace, ctx.trace.serve_spans) == \
        pytest.approx(step_host_ms.read(ctx) - 0.1)


def test_readers_find_nothing_without_the_programs_spans():
    ctx = _ctx(_trace(with_program=False))
    assert [r.read(ctx) for r in READERS] == [None] * len(READERS)
    assert stages.of(ctx.trace) is None


def test_idle_gaps_name_the_programs_stage():
    tr = _trace()
    gaps = {}
    for label, sec in stages.with_serve_spans(tr, tr.serve_spans) \
            .idle_gaps():
        gaps[label] = gaps.get(label, 0.0) + sec
    # the device is busy 3..6.25 ms: idle 0..3 (shed, step, pack,
    # upload, dispatch) and 6.25..10 (upload, dispatch, fetch, deliver,
    # step)
    assert gaps["serve.shed"] == pytest.approx(0.1e-3)
    assert gaps["serve.pack"] == pytest.approx(0.5e-3)
    assert gaps["serve.upload"] == pytest.approx(1.75e-3)
    assert gaps["serve.dispatch"] == pytest.approx(2e-3)
    assert gaps["serve.fetch"] == pytest.approx(1e-3)
    assert gaps["serve.deliver"] == pytest.approx(0.5e-3)
    assert gaps["serve.step"] == pytest.approx(0.9e-3)
    assert "bench.step" not in gaps
    # the benchmark's own labels are untouched
    assert "serve.step" not in dict(tr.idle_gaps())


def test_recorded_chip_trace():
    path = os.path.join(HERE, "recorded_trace_serve.json")
    with open(path) as f:
        rec = json.load(f)
    tr = tracing.Trace(ops=rec["ops"], modules=rec["modules"],
                       spans=rec["spans"])
    tr.serve_spans, tr.op_stages = rec["serve_spans"], rec["op_stages"]
    assert [o[:3] for o in tr.op_stages] == tr.ops
    # the scopes renamed no kernel: each tick that ends in the window
    # runs the parent's kernel calls
    whole = [m for m in tr.ticks() if m[1] + m[2] <= tr.t1]
    assert len(whole) >= 4
    for _, start, dur in whole:
        names = [o[0] for o in tr.ops if start <= o[1] < start + dur]
        for pattern, n in rec["expected_calls_per_tick"].items():
            rx = re.compile(getattr(kernels, pattern))
            assert sum(bool(rx.search(name)) for name in names) == n
    # ops with no stage, after their neighbours', are under 1%
    staged = stages.staged(tr.op_stages)
    assert sum(o[2] for o in staged if not o[3]) < 0.01 * sum(
        o[2] for o in staged)
    # each step's stages sit inside it, in order, with its tick
    order = ("serve.shed", "serve.pack", "serve.upload", "serve.dispatch",
             "serve.fetch", "serve.deliver")
    steps = [s for s in tr.serve_spans if s[0] == "serve.step"]
    assert [s[3]["tick"] for s in steps] == list(
        range(steps[0][3]["tick"], steps[0][3]["tick"] + len(steps)))
    for st in steps:
        inner = [s for s in tr.serve_spans if s[0] != "serve.step"
                 and st[1] <= s[1] and s[1] + s[2] <= st[1] + st[2]]
        names = [s[0] for s in sorted(inner, key=lambda s: s[1])]
        assert names == [n for n in order if n in names]
    ctx = _ctx(tr)
    values = {r.__name__.split(".")[-1]: r.read(ctx) for r in READERS}
    for name, want in rec["expected"].items():
        assert values[name] == pytest.approx(want, rel=1e-9)
    host = stages.host_ms(tr, tr.serve_spans)
    assert host == pytest.approx(step_host_ms.read(ctx), rel=0.05)
    labels = {label for label, _ in
              stages.with_serve_spans(tr, tr.serve_spans).idle_gaps()}
    assert any(label.startswith("serve.") for label in labels)
