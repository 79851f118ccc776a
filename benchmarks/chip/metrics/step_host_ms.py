"""Host staging and transfer: host time of ``FleetEngine.step`` outside
the time it blocks in ``EngineCore.fetch``, per dispatched tick, from
the benchmark's spans in the trace."""


def read(ctx):
    tr = ctx.trace
    steps = [(s, s + d) for n, s, d in tr.in_window(tr.spans)
             if n == "bench.step"]
    fetches = [(s, s + d) for n, s, d in tr.spans if n == "bench.fetch"]
    ticks = sum(n == "bench.dispatch" for n, _, _ in tr.in_window(tr.spans))
    if not steps or not ticks:
        return None
    host = 0
    for s, e in steps:
        host += (e - s) - sum(min(fe, e) - max(fs, s) for fs, fe in fetches
                              if fs < e and fe > s)
    return host / ticks / 1e6
