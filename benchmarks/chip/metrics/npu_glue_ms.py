"""Spiking backbone and heads: device time per tick of the operations in
the tick's ``npu`` stage scope that are not the NPU's kernels
(``kernels.NPU``): the im2col patches, pads, copies and reshapes around
them.  An operation with no scope in its metadata takes the stage of its
neighbours on the device's line where they agree (``stages.staged``).
The notes give device ms per tick by stage, the part of it so inferred,
and what stays without a stage, with its share of the device time."""
import re

import stages
from metrics.kernels import NPU


def read(ctx):
    found = stages.of(ctx.trace)
    ticks = len(ctx.trace.ticks())
    if found is None or not ticks:
        return None
    _, ops = found
    staged = stages.in_window(ctx.trace, stages.staged(ops))
    if not any(o[3] == "npu" for o in staged):
        return None
    by, inferred = {}, {}
    for _, _, d, stage, guess in staged:
        by[stage] = by.get(stage, 0) + d
        inferred[stage] = inferred.get(stage, 0) + d * guess
    total = sum(by.values())
    rx = re.compile(NPU)
    glue = sum(d for name, _, d, stage, _ in staged
               if stage == "npu" and not rx.search(name))
    ctx.notes.append(
        f"npu_glue_ms: device ms per tick by stage over {ticks} ticks: "
        + ", ".join(f"{k or 'no stage'} {v / ticks / 1e6:.6f} "
                    f"(inferred {inferred[k] / ticks / 1e6:.6f})"
                    for k, v in sorted(by.items()))
        + f"; no stage {100.0 * by.get('', 0) / total:.3f}% of device time")
    return glue / ticks / 1e6
