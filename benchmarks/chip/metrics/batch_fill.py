"""Admission and scheduling: how full the dispatched ticks were, in
percent: the requests the program's ``serve.pack`` spans packed (arg
``slots``) over dispatched ticks (``serve.dispatch`` spans) times the
tick batch."""
import stages


def read(ctx):
    found = stages.of(ctx.trace)
    if found is None:
        return None
    spans, _ = found
    ticks = stages.dispatched(ctx.trace, spans)
    if not ticks:
        return None
    slots = sum(s[3].get("slots", 0)
                for s in stages.in_window(ctx.trace, spans)
                if s[0] == "serve.pack")
    ctx.notes.append(f"batch_fill: {slots} requests packed into {ticks} "
                     f"ticks of {ctx.batch}")
    return 100.0 * slots / (ticks * ctx.batch)
