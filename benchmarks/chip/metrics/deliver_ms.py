"""Host staging and transfer: host time of the program's
``serve.deliver`` span (the health checks, result build and hand-off of
a harvested tick's requests, after its fetch) per harvested tick."""
import stages


def read(ctx):
    found = stages.of(ctx.trace)
    if found is None:
        return None
    spans, _ = found
    ms, n = stages.span_ms(ctx.trace, spans, "serve.deliver")
    return ms / n if n else None
