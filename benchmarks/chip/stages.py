"""The serving path's own spans and stage scopes, from a profiler trace.

The program opens ``serve.*`` host spans with integer args around the
stages of ``FleetEngine.step`` and the engine core's transfers
(``serve.step``, ``serve.shed``, ``serve.pack``, ``serve.upload``,
``serve.dispatch``, ``serve.fetch``, ``serve.deliver``), and names the
stages of its tick executable with ``jax.named_scope`` (``encode``,
``npu``, ``control``, ``isp``).  A scope reaches a TPU operation as the
``tf_op`` stat of the operation's event metadata, e.g.
``jit(_step)/npu/jit(_conv_lif_apply)/.../pad:``.

``jax.profiler.ProfileData`` shows an event's own stats but not those of
its metadata, so :func:`op_stage_by_name` reads them from the trace
file with the few fields of the XSpace schema it needs
(``tsl/profiler/protobuf/xplane.proto``).  :func:`events` gives plain
lists beside those of ``tracing.events_from_xplane``; everything after
that works on lists, so the readers can be checked without a chip
(``tests/test_stages.py``).

The harness does not keep these lists yet: ``tracing.Trace`` holds the
benchmark's ``bench.*`` spans and the operations' names only, so the
readers that use them (``metrics/pack_ms.py``, ``batch_fill.py``,
``deliver_ms.py``, ``npu_glue_ms.py``) read ``serve_spans`` and
``op_stages`` where a trace carries them and find nothing otherwise.
"""
from __future__ import annotations

import re

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

import tracing

SERVE_SPAN = re.compile(r"^serve\.")
STAGES = ("encode", "npu", "control", "isp")
NO_STAGE = ""


def _xspace_class():
    """``XSpace`` with only the fields read here; the parser skips the
    others.  Maps are declared as their wire form, repeated entries."""
    T = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="stages_xplane.proto",
                                           package="stages_xplane",
                                           syntax="proto3")

    def message(name, *fields):
        m = f.message_type.add(name=name)
        for number, fname, kind, type_name, repeated in fields:
            field = m.field.add(name=fname, number=number, type=kind,
                                label=T.LABEL_REPEATED if repeated
                                else T.LABEL_OPTIONAL)
            if type_name:
                field.type_name = type_name

    ref = ".stages_xplane."
    message("XStat", (1, "metadata_id", T.TYPE_INT64, "", False),
            (5, "str_value", T.TYPE_STRING, "", False),
            (7, "ref_value", T.TYPE_UINT64, "", False))
    message("XEventMetadata", (2, "name", T.TYPE_STRING, "", False),
            (5, "stats", T.TYPE_MESSAGE, ref + "XStat", True))
    message("XStatMetadata", (2, "name", T.TYPE_STRING, "", False))
    message("EventMetadataEntry", (1, "key", T.TYPE_INT64, "", False),
            (2, "value", T.TYPE_MESSAGE, ref + "XEventMetadata", False))
    message("StatMetadataEntry", (1, "key", T.TYPE_INT64, "", False),
            (2, "value", T.TYPE_MESSAGE, ref + "XStatMetadata", False))
    message("XPlane", (2, "name", T.TYPE_STRING, "", False),
            (4, "event_metadata", T.TYPE_MESSAGE,
             ref + "EventMetadataEntry", True),
            (5, "stat_metadata", T.TYPE_MESSAGE,
             ref + "StatMetadataEntry", True))
    message("XSpace", (1, "planes", T.TYPE_MESSAGE, ref + "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("stages_xplane.XSpace"))


def stage_of(tf_op: str) -> str:
    """The stage scope in an operation's ``tf_op`` path, or ``""``."""
    for part in tf_op.split("/"):
        if part in STAGES:
            return part
    return NO_STAGE


def op_stage_by_name(path: str, device: int = 0) -> dict:
    """``{event name: stage}`` for the chosen TPU's operations, from the
    ``tf_op`` stat of each event's metadata (the event's name is its
    metadata's name, the whole HLO instruction)."""
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    want = f"/device:TPU:{device}"
    out = {}
    for plane in space.planes:
        if not (plane.name == want or plane.name.startswith(want + " ")):
            continue
        tf_op = {e.key for e in plane.stat_metadata
                 if e.value.name == "tf_op"}
        for entry in plane.event_metadata:
            md = entry.value
            out[md.name] = next((stage_of(s.str_value) for s in md.stats
                                 if s.metadata_id in tf_op), NO_STAGE)
    return out


def events(path: str, device: int = 0) -> dict:
    """Plain lists from a trace file, beside ``tracing.events_from_xplane``
    's: ``serve_spans``, the program's host spans ``[name, start_ns,
    duration_ns, args]``, and ``op_stages``, the same TPU operations as
    its ``ops`` with their stage, ``[name, start_ns, duration_ns,
    stage]``."""
    from jax.profiler import ProfileData
    stage = op_stage_by_name(path, device)
    pd = ProfileData.from_file(path)
    spans, ops = [], []
    want = f"/device:TPU:{device}"
    for plane in pd.planes:
        if plane.name == want or plane.name.startswith(want + " "):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [[tracing.op_name(e.name), e.start_ns,
                             e.duration_ns,
                             stage.get(e.name, NO_STAGE)]
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns,
                           {k: int(v) for k, v in e.stats}]
                          for e in line.events if SERVE_SPAN.match(e.name)]
    return {"serve_spans": spans, "op_stages": ops}


# ------------------------------------------------------------ reductions

def of(trace):
    """``(serve_spans, op_stages)`` of a trace, or None where it carries
    neither (a trace of a program without them, or a harness that does
    not keep them)."""
    spans = getattr(trace, "serve_spans", None) or []
    ops = getattr(trace, "op_stages", None) or []
    return (spans, ops) if spans or ops else None


def in_window(trace, items):
    return [x for x in items if trace.t0 <= x[1] < trace.t1]


def dispatched(trace, spans) -> int:
    """Ticks dispatched in the window: its ``serve.dispatch`` spans."""
    return sum(s[0] == "serve.dispatch" for s in in_window(trace, spans))


def span_ms(trace, spans, name):
    """(total ms, count) of the window's spans called ``name``."""
    hits = [s[2] for s in in_window(trace, spans) if s[0] == name]
    return sum(hits) / 1e6, len(hits)


def host_ms(trace, spans):
    """``serve.step`` time outside ``serve.fetch``, ms per dispatched
    tick: what ``step_host_ms`` measures from the benchmark's wrappers,
    seen from inside the program."""
    steps = [(s, s + d) for n, s, d, _ in in_window(trace, spans)
             if n == "serve.step"]
    fetches = [(s, s + d) for n, s, d, _ in spans if n == "serve.fetch"]
    ticks = dispatched(trace, spans)
    if not steps or not ticks:
        return None
    host = sum((e - s) - sum(min(fe, e) - max(fs, s) for fs, fe in fetches
                             if fs < e and fe > s) for s, e in steps)
    return host / ticks / 1e6


def staged(op_stages) -> list:
    """The operations by start time, each ``[name, start_ns, duration_ns,
    stage, inferred]``.  An operation whose metadata names no stage (XLA's
    own layout copies, and fusions it builds from a concatenation, such as
    the im2col patches) takes the stage of the scoped operations on both
    sides of it on the device's line when they agree (``inferred`` True);
    otherwise it keeps ``""``."""
    ops = sorted(op_stages, key=lambda o: o[1])
    out, prev = [], NO_STAGE
    pending = []                    # unscoped since the last scoped op
    for name, s, d, stage in ops:
        if stage == NO_STAGE:
            pending.append([name, s, d, NO_STAGE, False])
            continue
        if prev == stage:
            for p in pending:
                p[3], p[4] = stage, True
        out += pending
        pending = []
        out.append([name, s, d, stage, False])
        prev = stage
    return out + pending


class WithServeSpans(tracing.Trace):
    """A trace with the program's spans beside the benchmark's, for its
    idle gaps: a piece of the window is labelled by the innermost of the
    program's spans open in it, else by the innermost of the
    benchmark's.  (The benchmark's wrappers of ``upload`` and
    ``dispatch`` hold the program's spans of them; the program's
    ``serve.fetch`` holds the wrapper of ``fetch``.)"""

    def labels(self):
        marks = sorted([(s, 1, -d, n) for n, s, d in self.spans]
                       + [(s + d, 0, 0, n) for n, s, d in self.spans])
        out, stack, prev = [], [], None
        for t, opening, _, name in marks:
            if prev is not None and t > prev:
                ours = [n for n in stack if SERVE_SPAN.match(n)]
                out.append((prev, t, ours[-1] if ours
                            else stack[-1] if stack else "no span"))
            prev = t
            if opening:
                stack.append(name)
            elif name in stack:
                del stack[len(stack) - 1 - stack[::-1].index(name)]
        return out


def with_serve_spans(trace, spans) -> WithServeSpans:
    return WithServeSpans(ops=trace.ops, modules=trace.modules,
                          spans=trace.spans + [s[:3] for s in spans])
