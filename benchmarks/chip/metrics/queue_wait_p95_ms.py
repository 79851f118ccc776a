"""Admission and scheduling: due time -> packed into a tick
(``RequestTelemetry.t_admit``), 95th percentile by nearest rank, over
the requests due before the profiler started (tracing slows the host).
A request never admitted waits forever.  Open loops only: a closed
loop has no due time."""
import math

from harness import nearest_rank


def read(ctx):
    if not ctx.cell.open_loop:
        return None
    waits = [1e3 * (r.t_admit - r.t_ref) if r.status == "done" else math.inf
             for r in ctx.records if r.t_ref < ctx.untraced_until]
    return nearest_rank(waits, 0.95) if waits else None
