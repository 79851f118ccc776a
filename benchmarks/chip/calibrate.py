#!/usr/bin/env python3
"""Readings that the correctness limits are set from, in one process.

    python3 benchmarks/chip/calibrate.py --workload yolo64.rain \
        --seconds 3 --seeds 101 102 103 ...

For each seed: builds the cell with that seed's weights and traffic,
serves a short window at the cell's own load, and compares the sampled
results with the reference exactly as a benchmark run does (the
program's readings).  Then the control: the reference itself, computed
at the next precision below the configuration's (``"high"``, three
bf16 passes, where the configuration states float32 at ``"highest"``),
put in the program's place on the same sampled requests and compared
the same way (the control is a pure function of its inputs, so it has
no twin gap).  One JSON line per seed.  The program's readings set each
limit's lower end, the control's its upper end (see ``PERF.md``).  Each
line also holds the per-request gaps and reference margins.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

LOWER = {"highest": "high"}


def serve_sample(cell, seed: int, seconds: float):
    """One short served window; returns (inputs, entries, outputs,
    twins) as ``check.compare`` takes them."""
    import harness
    import check
    import traffic
    cfg = cell.config
    sched = traffic.Schedule(cell.mix, seed, seconds)
    params = harness.make_weights(cfg, seed)
    engine = harness.build_engine(cfg, params)
    inputs = harness.Inputs(cfg, cell.mix, seed)
    harness.warm(engine, inputs, sched)
    sample = check.draw_sample(seed, cell, sched)
    runner = harness.Run(engine, inputs, sched, sample)
    t0 = time.perf_counter() + 0.005
    if cell.open_loop:
        runner.open_loop(t0, seconds)
    else:
        runner.closed_loop(t0, seconds, int(cell.mix["outstanding"]))
    entries = {r: runner.records[r].entry for r in runner.outputs}
    return inputs, entries, runner.outputs, runner.twins


def readings(cell, seed: int, seconds: float) -> dict:
    import numpy as np
    import check
    import harness
    cfg = cell.config
    departure = check.checks(cfg)["departure"]
    inputs, entries, outputs, twins = serve_sample(cell, seed, seconds)
    rids = sorted(outputs)
    events, bayer = inputs.payload([entries[r] for r in rids])
    served = [np.stack([outputs[r][k] for r in rids]) for k in range(3)]
    params = harness.make_weights(cfg, seed)
    program = check.gaps(cfg, params, events, bayer, *served)
    pairs = check.twin_gaps(entries, outputs, twins)
    low = LOWER[cfg["matmul_precision"]]
    raw, ctl, _, _ = check.reference_outputs(cfg, params, events, bayer, low)
    rgb = check.reference_isp(cfg, bayer, ctl, low)
    control = check.gaps(cfg, params, events, bayer, raw, ctl, rgb)
    gaps = lambda g: {k: np.asarray(v).tolist() for k, v in g.items()}
    return {"seed": seed, "compared": len(rids), "twin_pairs": len(pairs),
            "program": dict(check.numbers(program, departure),
                            twin_gap=float(np.max(pairs)) if pairs
                            else 0.0),
            "control": check.numbers(control, departure),
            "program_gaps": gaps(program), "control_gaps": gaps(control),
            "control_precision": low}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import harness
    import run
    cell = harness.load_cell(args.workload)
    run.devices_for(cell)
    run.enable_compile_cache()
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
