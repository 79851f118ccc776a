#!/usr/bin/env python3
"""Chip benchmark of the cognitive perception service.

    python3 benchmarks/chip/run.py --workload yolo64.rain --seed 7 \
        --seconds 10 --trace 0

Runs one cell of ``BENCHMARK.json`` on the chip it is started on: makes
the weights and the traffic from ``--seed``, warms the cell's tick up,
drives ``FleetEngine`` for ``--seconds`` seconds, drains, checks a
seeded sample of the served results against the plain reference, and
prints one JSON line last on standard output.  ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` profiles the window and reports
its per-layer metrics.  It refuses to run without a TPU.

``--keep-trace PATH`` writes the traced run's event lists as JSON (for
the recorded trace the reduction's test reads); the benchmark's own
runs do not pass it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse      # noqa: E402
import gc            # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


class NoChip(RuntimeError):
    pass


def devices_for(cell):
    """The devices this cell runs on; refuses anything but enough TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} chips, JAX "
                     f"found {len(devs)}")
    return devs


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``<checkout>/.jax_cache``, a fixed path,
    unless ``JAX_COMPILATION_CACHE_DIR`` already names one."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Counts traces and backend compiles while ``on``."""

    def __init__(self):
        import jax
        self.on, self.n = False, 0

        def listen(name, *_, **__):
            if self.on and name in ("/jax/core/compile/jaxpr_trace_duration",
                                    "/jax/core/compile/"
                                    "backend_compile_duration"):
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


def run(args, cell, devices, log=print):
    import check
    import harness
    import tracing
    import traffic

    cache = enable_compile_cache()
    counter = CompileCounter()
    cfg, mix = cell.config, cell.mix
    phases = [("start", time.perf_counter() - T_PROCESS)]
    sched = traffic.Schedule(mix, args.seed, args.seconds)
    params = harness.make_weights(cfg, args.seed)
    engine = harness.build_engine(cfg, params)
    phases.append(("weights and engine", time.perf_counter() - T_PROCESS))
    inputs = harness.Inputs(cfg, mix, args.seed)
    phases.append(("traffic", time.perf_counter() - T_PROCESS))
    harness.warm(engine, inputs, sched)
    phases.append(("warm-up", time.perf_counter() - T_PROCESS))
    log("set-up, seconds since process start: " + ", ".join(
        f"{name} {t:.2f}" for name, t in phases), file=sys.stderr)
    sample = check.draw_sample(args.seed, cell, sched)
    spans = tracing.Spans(engine) if args.trace else None
    runner = harness.Run(engine, inputs, sched, sample)
    if spans is not None:
        runner.submit = spans.wrap("bench.submit", runner.submit)
    tracer = tracing.Tracer(os.path.join(ROOT, ".bench_trace", cell.name),
                            args.seconds) if args.trace else None

    gc.collect()
    gc.disable()
    counter.on = True
    t0 = time.perf_counter() + 0.005
    setup_s = t0 - T_PROCESS
    if cell.open_loop:
        runner.open_loop(t0, args.seconds,
                         tracer.poll if tracer else None)
    else:
        runner.closed_loop(t0, args.seconds, int(mix["outstanding"]),
                           tracer.poll if tracer else None)
    counter.on = False
    gc.enable()
    if tracer is not None:
        tracer.stop()
    t_close = t0 + args.seconds

    records = [r for r in runner.records.values() if r.t_ref < t_close]
    lat = harness.latencies_ms(records)
    failed = sum(r.status != "done" for r in records)
    late = harness.nearest_rank(runner.lateness, 0.95) * 1e3 \
        if runner.lateness else 0.0
    log(f"window: {len(records)} requests sent, {failed} not delivered, "
        f"{engine.ticks} ticks in all, generator late p95 "
        f"{late:.3f} ms, compilations in the window {counter.n}, "
        f"longest step {1e3 * runner.longest_step:.1f} ms, "
        f"{runner.slow_steps} steps over 50 ms", file=sys.stderr)
    metrics = {}
    if not args.trace:
        for m in cell.end_to_end:
            v = {"latency_p50_ms": lambda: harness.nearest_rank(lat, 0.5),
                 "latency_p95_ms": lambda: harness.nearest_rank(lat, 0.95),
                 "delivered_rps": lambda: sum(
                     r.t_deliver < t_close for r in records
                     if r.status == "done") / args.seconds,
                 "setup_s": lambda: setup_s}[m["name"]]()
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        over = sum(x > 1e3 / cfg["assumed"]["window_hz"] for x in lat)
        log(f"share over one window period: {over}/{len(lat)}",
            file=sys.stderr)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int((devices[0].memory_stats() or {})
                                       .get("peak_bytes_in_use", 0))}
    breakdown = None
    if args.trace:
        trace = tracer.reduce(getattr(args, "keep_trace", None))
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        ctx = tracing.Context(cell=cell, trace=trace, records=records,
                              device_kind=devices[0].device_kind,
                              batch=engine.batch,
                              untraced_until=t0 + tracer.start_at)
        for m in cell.per_layer:
            v = tracing.read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        ctx.notes.append("profiler start, stop: " + ", ".join(
            f"{s:.2f} s" for s in tracer.stall_s))
        for line in ctx.notes:
            log(line, file=sys.stderr)
        breakdown = trace.breakdown()

    # the reference runs once the window's state is gone
    engine_outputs, twins = runner.outputs, runner.twins
    entries = {rid: runner.records[rid].entry for rid in engine_outputs}
    undelivered = sum(r.rid in sample and r.status not in ("done",
                                                           "rejected")
                      for r in records)
    del runner, engine, params
    gc.collect()
    log(f"compile cache: {cache}", file=sys.stderr)
    verdict = check.compare(cfg, args.seed, inputs, entries, engine_outputs,
                            twins, undelivered)
    result = {"correct": verdict.correct, "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checked"] = verdict.numbers
    for line in verdict.lines():
        log(line, file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the trace's event lists to this file")
    args = ap.parse_args(argv)
    import harness
    cell = harness.load_cell(args.workload)
    try:
        devices = devices_for(cell)
    except NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    result = run(args, cell, devices, log=print)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
