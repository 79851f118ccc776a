"""Spans, the profiler trace, and its reduction to per-layer metrics.

The benchmark's spans are ``jax.profiler.TraceAnnotation`` ranges that
its own code opens around the calls it makes into the program
(``bench.submit``, ``bench.step``) and around the engine core's
``upload``, ``dispatch`` and ``fetch``, wrapped on the engine object.
They land in the profiler's trace beside the device's operations, on
the same clock.

:func:`events_from_xplane` turns a trace file into plain lists (the
device's operations, the tick executions, the host spans); everything
after that works on those lists, so the reduction can be checked on a
small recorded trace without a chip (``tests/test_trace.py``).
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import importlib
import json
import os
import re
import shutil
import time

HOST_SPAN = re.compile(r"^bench\.")
TOP = 10
TRACE_S = 5.0


class Spans:
    """Wraps the engine's calls in profiler spans (host side only)."""

    def __init__(self, engine):
        from jax.profiler import TraceAnnotation
        self.annotation = TraceAnnotation
        engine.step = self.wrap("bench.step", engine.step)
        for core in engine.cores:
            for name in ("upload", "dispatch", "fetch"):
                setattr(core, name,
                        self.wrap("bench." + name, getattr(core, name)))

    def wrap(self, name, fn):
        ann = self.annotation

        @functools.wraps(fn)
        def wrapped(*a, **k):
            with ann(name):
                return fn(*a, **k)
        return wrapped


class Tracer:
    """Profiles the last ``TRACE_S`` seconds of the window.  Tracing
    slows the host's steps by about half again, so the requests due
    before the profiler started are the ones the admission metric
    reads; stopping it stalls the host for about four seconds per traced
    second, so it stops at the first turn after the window has
    closed."""

    def __init__(self, directory: str, seconds: float):
        self.dir = directory
        self.seconds = seconds
        self.start_at = max(0.0, seconds - TRACE_S)
        self.state = "waiting"
        self.stall_s = []               # seconds taken to start, to stop

    def poll(self, now):
        """Called with the seconds since the window opened."""
        import jax
        if self.state == "waiting" and now >= self.start_at:
            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir)
            # host level 1 keeps the benchmark's spans and drops the
            # runtime's own host events
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level, opts.python_tracer_level = 1, 0
            t = time.perf_counter()
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.stall_s.append(time.perf_counter() - t)
            self.state = "active"
        elif self.state == "active" and now >= self.seconds:
            self.stop()

    def stop(self):
        if self.state == "active":
            import jax
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.stall_s.append(time.perf_counter() - t)
            self.state = "done"

    def reduce(self, keep: str | None = None) -> "Trace":
        """The trace's event lists; ``keep`` also writes them, as JSON, to
        that path (for a recorded trace)."""
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace in {self.dir}, found "
                               f"{len(files)}")
        events = events_from_xplane(files[0])
        shutil.rmtree(self.dir, ignore_errors=True)
        if keep:
            with open(keep, "w") as f:
                json.dump(events, f)
        return Trace(**events)


def op_name(text: str) -> str:
    """The HLO instruction's name from a trace event's name, which holds
    the whole instruction (``%name = f32[...] op(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def events_from_xplane(path: str, device: int = 0) -> dict:
    """Plain lists from a trace file: ``ops`` (the HLO instructions run,
    by name; the line of asynchronous copies is left out) and ``modules``
    (executions of jitted programs), each ``[name, start_ns,
    duration_ns]`` on the chosen TPU, and ``spans``, the benchmark's host
    spans, on the same clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    want = f"/device:TPU:{device}"
    for plane in pd.planes:
        if plane.name == want or plane.name.startswith(want + " "):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [[op_name(e.name), e.start_ns, e.duration_ns]
                            for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events if HOST_SPAN.match(e.name)]
    return {"ops": ops, "modules": modules, "spans": spans}


def union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Trace:
    ops: list
    modules: list
    spans: list

    def __post_init__(self):
        steps = [(s, s + d) for n, s, d in self.spans if n == "bench.step"]
        if not steps or not self.ops:
            raise RuntimeError("the trace holds no step spans or no "
                               "device operations")
        self.t0 = min(s for s, _ in steps)
        self.t1 = max(e for _, e in steps)
        clip = lambda s, d: (max(s, self.t0), min(s + d, self.t1))
        self.busy = [iv for iv in union(
            clip(s, d) for _, s, d in self.ops) if iv[1] > iv[0]]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy) / 1e9

    def in_window(self, items):
        return [x for x in items if self.t0 <= x[1] < self.t1]

    def ticks(self) -> list:
        """Tick executions (the engine's one jitted step) started in the
        window: ``[name, start_ns, duration_ns]``."""
        return [m for m in self.in_window(self.modules)
                if re.search(r"_step", m[0])]

    def kernel_ns(self, pattern: str) -> tuple:
        """(total ns, calls) of the device operations in the window whose
        instruction name matches ``pattern``."""
        rx = re.compile(pattern)
        hits = [d for t, s, d in self.in_window(self.ops) if rx.search(t)]
        return sum(hits), len(hits)

    def labels(self):
        """The window cut into ``(start, end, label)`` pieces, each
        labelled by the innermost host span open in it (the benchmark's
        spans nest: they are opened and closed on one thread)."""
        marks = sorted([(s, 1, -d, n) for n, s, d in self.spans]
                       + [(s + d, 0, 0, n) for n, s, d in self.spans])
        out, stack, prev = [], [], None
        for t, opening, _, name in marks:
            if prev is not None and t > prev:
                out.append((prev, t, stack[-1] if stack else "no span"))
            prev = t
            if opening:
                stack.append(name)
            elif name in stack:
                del stack[len(stack) - 1 - stack[::-1].index(name)]
        return out

    def idle_gaps(self):
        """Idle time between device operations in the window, as
        ``(label, seconds)`` pieces labelled by :meth:`labels`."""
        gaps, prev = [], self.t0
        for s, e in self.busy + [[self.t1, self.t1]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        pieces, out, j = self.labels(), [], 0
        for s, e in gaps:
            while j < len(pieces) and pieces[j][1] <= s:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < e:
                a, b = max(s, pieces[k][0]), min(e, pieces[k][1])
                out.append((pieces[k][2], (b - a) / 1e9))
                k += 1
        return out

    def breakdown(self) -> dict:
        per_op, per_gap = {}, {}
        for name, s, d in self.in_window(self.ops):
            per_op[name] = per_op.get(name, 0.0) + d / 1e9
        for label, sec in self.idle_gaps():
            per_gap[label] = per_gap.get(label, 0.0) + sec
        top = lambda d: sorted(([k, v] for k, v in d.items()),
                               key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": top(per_op), "idle_gaps": top(per_gap)}


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets."""
    cell: object
    trace: Trace
    records: list
    device_kind: str
    batch: int
    untraced_until: float       # host-clock time the profiler started
    notes: list = dataclasses.field(default_factory=list)


def read_metric(name: str, ctx: Context):
    """Runs ``metrics/<name>.py``'s ``read(ctx)``; None where it finds
    nothing to read."""
    mod = importlib.import_module("metrics." + name.replace(".", "_"))
    return mod.read(ctx)
