# One function per paper table. Prints ``name,us_per_call,derived`` CSV
# and persists every row to BENCH_<n>.json so the perf trajectory is
# recorded across PRs (n auto-increments; artifacts are gitignored).
#
#   backbones.py  -> paper §IV-C backbone table (AP@0.5 + sparsity)
#   isp_bench.py  -> paper §V ISP pipeline stage timings
#   npu_bench.py  -> paper §IV NPU event throughput / sparsity +
#                    jnp-vs-pallas backend sweep (lif / dense /
#                    backbone / engine tick)
#   kernel_bench  -> Pallas kernel / tile-skip stats (§VI adaptation)
#   roofline      -> EXPERIMENTS.md §Roofline table from the dry-run
#
# ``--smoke``: run every bench once (REPRO_BENCH_SMOKE=1 collapses reps
# and training loops) and validate the emitted JSON — the CI lane.
# ``--tune-smoke``: bound the kernel-autotuner sweeps to the "smoke"
# TuneConfig (REPRO_TUNE_SMOKE=1: fewer reps, harder roofline pruning)
# without collapsing the bench timings themselves — the CI bench lane
# passes both so the tuned rows are measured but the sweep stays cheap.
import glob
import json
import os
import re
import sys
import time

BENCH_SCHEMA_VERSION = 1
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def next_bench_path(root: str = _ROOT) -> str:
    """BENCH_<n>.json with the smallest unused n (monotone log)."""
    taken = set()
    for p in glob.glob(os.path.join(root, "BENCH_*.json")):
        m = re.fullmatch(r"BENCH_(\d+)\.json", os.path.basename(p))
        if m:
            taken.add(int(m.group(1)))
    n = 0
    while n in taken:
        n += 1
    return os.path.join(root, f"BENCH_{n}.json")


def validate_bench(doc: dict) -> None:
    """Schema check for a persisted bench file; raises ValueError with
    the first violation (the CI smoke lane runs this on its output)."""
    if not isinstance(doc, dict):
        raise ValueError("bench doc must be a JSON object")
    for key, typ in (("schema", int), ("created_unix", (int, float)),
                     ("smoke", bool), ("rows", list)):
        if key not in doc:
            raise ValueError(f"bench doc missing {key!r}")
        if not isinstance(doc[key], typ):
            raise ValueError(f"bench doc {key!r} has type "
                             f"{type(doc[key]).__name__}")
    if doc["schema"] != BENCH_SCHEMA_VERSION:
        raise ValueError(f"unknown bench schema {doc['schema']}")
    if not doc["rows"]:
        raise ValueError("bench doc has no rows")
    for i, row in enumerate(doc["rows"]):
        if not isinstance(row, dict):
            raise ValueError(f"row {i} is not an object")
        if not isinstance(row.get("name"), str) or not row["name"]:
            raise ValueError(f"row {i} has no name")
        if not isinstance(row.get("us_per_call"), (int, float)):
            raise ValueError(f"row {row['name']!r}: us_per_call must be "
                             f"a number")
        if not isinstance(row.get("derived"), str):
            raise ValueError(f"row {row['name']!r}: derived must be a "
                             f"string")
    names = [r["name"] for r in doc["rows"]]
    dup = {n for n in names if names.count(n) > 1}
    if dup:
        raise ValueError(f"duplicate row names: {sorted(dup)}")


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    if smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    if "--tune-smoke" in argv:
        os.environ["REPRO_TUNE_SMOKE"] = "1"
    # --only <module>[,<module>...]: run a subset of the bench suite
    # (e.g. the CI serving-smoke lane runs ``--only serve`` under 8
    # forced host devices).  "npu" still includes the serving sweep it
    # hosts; "serve" runs that sweep alone.
    only = None
    for i, a in enumerate(argv):
        if a == "--only" and i + 1 < len(argv):
            only = set(argv[i + 1].split(","))
        elif a.startswith("--only="):
            only = set(a.split("=", 1)[1].split(","))

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    rows = []
    print("name,us_per_call,derived")

    def emit(name, us, derived=""):
        print(f"{name},{us:.1f},{derived}")
        sys.stdout.flush()
        rows.append({"name": name, "us_per_call": float(us),
                     "derived": str(derived)})

    from benchmarks import backbones, isp_bench, kernel_bench, npu_bench, \
        roofline_bench, serve_bench, soak_bench, train_bench
    modules = {"isp": isp_bench, "npu": npu_bench, "kernel": kernel_bench,
               "backbones": backbones, "roofline": roofline_bench,
               "serve": serve_bench, "soak": soak_bench,
               "train": train_bench}
    if only is not None:
        unknown = only - set(modules)
        if unknown:
            raise SystemExit(f"--only: unknown modules {sorted(unknown)}; "
                             f"pick from {sorted(modules)}")
        if "npu" in only:
            only.discard("serve")   # npu hosts the serving sweep; running
                                    # both would emit duplicate rows
        for name in ("isp", "npu", "kernel", "backbones", "roofline",
                     "serve", "soak", "train"):
            if name in only:
                modules[name].run(emit)
    else:
        isp_bench.run(emit)
        npu_bench.run(emit)
        kernel_bench.run(emit)
        backbones.run(emit)
        roofline_bench.run(emit)
        train_bench.run(emit)
        soak_bench.run(emit)

    doc = {"schema": BENCH_SCHEMA_VERSION, "created_unix": time.time(),
           "smoke": smoke, "rows": rows}
    path = next_bench_path()
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    validate_bench(json.load(open(path)))      # round-trip check
    print(f"# wrote {os.path.basename(path)} ({len(rows)} rows)",
          file=sys.stderr)


if __name__ == '__main__':
    main()
