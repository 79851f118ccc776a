#!/usr/bin/env python3
"""Find an open-loop configuration's knee on the chip, in one process.

    python3 benchmarks/chip/sweep.py --workload yolo64.rain --seed 3 \
        --seconds 8 --streams 20 40 60 80

For each stream count, runs the cell's traffic for ``--seconds`` on one
engine (built and warmed once) and prints the latency median and 95th
percentile from the due time, the requests still in the system when
the window closed, and the 95th percentile of the last third of the
window against the first third (a backlog that grows shows as a rising
tail).  The knee is the highest count whose 95th percentile is within
one window period and whose backlog does not grow; a cell runs at about
four fifths of it.  The correctness check is not run here.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--streams", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import time

    import harness
    import run
    import traffic
    cell = harness.load_cell(args.workload)
    run.devices_for(cell)
    run.enable_compile_cache()
    cfg = cell.config
    params = harness.make_weights(cfg, args.seed)
    engine = harness.build_engine(cfg, params)
    inputs = harness.Inputs(cfg, cell.mix, args.seed)
    period = cell.mix["period_ms"]
    for n in args.streams:
        sched = traffic.Schedule(dict(cell.mix, streams=n), args.seed,
                                 args.seconds)
        harness.warm(engine, inputs, sched, ticks=2)
        runner = harness.Run(engine, inputs, sched, set())
        t0 = time.perf_counter() + 0.005
        close = {}

        def at_close(now, runner=runner, close=close):
            if now >= args.seconds and "live" not in close:
                close["live"] = len(runner.live)
        runner.open_loop(t0, args.seconds, at_close)
        recs = list(runner.records.values())
        lat = harness.latencies_ms(recs)
        third = args.seconds / 3
        first = harness.latencies_ms([r for r in recs
                                      if r.t_ref - t0 < third])
        last = harness.latencies_ms([r for r in recs
                                     if r.t_ref - t0 >= 2 * third])
        row = {"streams": n, "requests": len(recs),
               "failed": sum(r.status != "done" for r in recs),
               "p50_ms": harness.nearest_rank(lat, 0.5),
               "p95_ms": harness.nearest_rank(lat, 0.95),
               "p95_first_third_ms": harness.nearest_rank(first, 0.95),
               "p95_last_third_ms": harness.nearest_rank(last, 0.95),
               "live_at_close": close.get("live"),
               "late_p95_ms": 1e3 * harness.nearest_rank(runner.lateness,
                                                         0.95),
               "within_period": bool(harness.nearest_rank(lat, 0.95)
                                     <= period)}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
