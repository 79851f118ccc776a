#!/usr/bin/env python3
"""Spread of a set of runs, as the bounds are set from it.

    python3 benchmarks/chip/spread.py set1.jsonl set2.jsonl

Each file holds the result lines of one set of runs of one cell (the
last standard-output line of each run).  For every metric, prints each
set's median and its spread: the distance between the first and third
quartile (``statistics.quantiles(values, n=4)``) as a share of the
median, and the wider of the sets' spreads.
"""
from __future__ import annotations

import json
import statistics
import sys


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths) -> int:
    sets = []
    for p in paths:
        with open(p) as f:
            sets.append([json.loads(line) for line in f if line.strip()])
    names = sorted({m for s in sets for r in s for m in r["metrics"]})
    for m in names:
        row = []
        for s in sets:
            v = [r["metrics"][m]["value"] for r in s if m in r["metrics"]]
            row.append((statistics.median(v), spread(v), len(v)))
        print(json.dumps({"metric": m, "sets": row,
                          "widest": max(x[1] for x in row)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
