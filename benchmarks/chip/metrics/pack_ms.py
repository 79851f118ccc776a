"""Admission and scheduling: host time of the program's ``serve.pack``
span (the packing loop of ``FleetEngine.step``) per dispatched tick.
The notes give every ``serve.*`` span's host ms per dispatched tick, and
``serve.step`` outside ``serve.fetch``: the program's own view of
``step_host_ms``."""
import stages

SPANS = ("serve.step", "serve.shed", "serve.pack", "serve.upload",
         "serve.dispatch", "serve.fetch", "serve.deliver")


def read(ctx):
    found = stages.of(ctx.trace)
    if found is None:
        return None
    spans, _ = found
    ticks = stages.dispatched(ctx.trace, spans)
    ms, n = stages.span_ms(ctx.trace, spans, "serve.pack")
    if not ticks or not n:
        return None
    per = {name: stages.span_ms(ctx.trace, spans, name)[0] / ticks
           for name in SPANS}
    ctx.notes.append(
        f"pack_ms: {ticks} dispatched ticks; host ms per tick by span: "
        + ", ".join(f"{k} {v:.6f}" for k, v in per.items())
        + f"; serve.step outside serve.fetch "
        f"{stages.host_ms(ctx.trace, spans):.6f}")
    return ms / ticks
