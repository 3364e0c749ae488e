#!/usr/bin/env python3
"""Spread of a cell's metrics over sets of runs, as the bounds are set.

    python3 benchmark/tools/spread.py <set1 result files> -- <set2 result files>

Each file holds a run's output; its last line is the result.  For each
set and metric: the median and the spread, the distance between the
first and third quartile (``statistics.quantiles(values, n=4)``) as a
share of the median; then the wider of the sets' spreads and five
times it, the bound it suggests.
"""

import json
import statistics
import sys


def result(path: str) -> dict:
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    return json.loads(lines[-1])


def spreads(results: list) -> dict:
    out = {}
    names = sorted({m for r in results for m in r["metrics"]})
    for m in names:
        xs = [r["metrics"][m]["value"] for r in results if m in r["metrics"]]
        med = statistics.median(xs)
        q1, _, q3 = statistics.quantiles(xs, n=4)
        out[m] = {"n": len(xs), "median": med, "spread": (q3 - q1) / med,
                  "min": min(xs), "max": max(xs)}
    return out


def main(argv) -> int:
    sets, cur = [], []
    for a in argv:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    per = [spreads([result(p) for p in s]) for s in sets if s]
    for i, s in enumerate(per, 1):
        for m, v in s.items():
            print(f"set {i} {m}: " + json.dumps(v))
    for m in per[0]:
        wide = max(s[m]["spread"] for s in per if m in s)
        print(f"{m}: widest spread {wide:.5f}, 5x = {5 * wide:.5f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
