"""Admission and scheduling: the share of requests delivered more than
one window period (``assumed.window_hz``) after their due time, or never,
in percent, over the requests due before the profiler started (tracing
slows the host).  It reads the tail that the open loops' median cannot
see: a stall of the host's step refuses or delays the requests due
during it.  Open loops only: a closed loop has no due time."""


def read(ctx):
    if not ctx.cell.open_loop:
        return None
    period_s = 1.0 / ctx.cell.config["assumed"]["window_hz"]
    due = [r for r in ctx.records if r.t_ref < ctx.untraced_until]
    if not due:
        return None
    late = sum(not (r.status == "done" and r.t_deliver - r.t_ref <= period_s)
               for r in due)
    return 100.0 * late / len(due)
