#!/usr/bin/env python3
"""Spread of a cell's end-to-end metrics over two sets of runs, and the
bound it gives.

    python3 benchmarks/chip/tools/spread.py runs.jsonl [--set-size 6]

``runs.jsonl`` holds one run's result line per line, each with an added
``"seed"`` key; the first ``set-size`` untraced lines of a cell are its
first set, the next ``set-size`` its second.  A spread is the distance
between the first and third quartile (``statistics.quantiles(n=4)``) as
a share of the median; the bound is five times the wider of the two
sets' spreads, and never under 1%.  It also prints what a check reads
against a bound: for tightness the mean of the two sets' spreads, each
set without its run farthest from the median (the bound must be over
twice it), and for looseness the wider spread of all the runs (a bound
over 1% must be at most eight times it).
"""
import argparse
import collections
import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed(values):
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("runs")
    ap.add_argument("--set-size", type=int, default=6)
    args = ap.parse_args(argv)
    by_cell = collections.defaultdict(list)
    with open(args.runs) as f:
        for line in f:
            row = json.loads(line)
            if "busy_s" in row["device"]:
                continue
            by_cell[row["workload"]].append(row)
    k = args.set_size
    for cell, rows in by_cell.items():
        sets = [rows[:k], rows[k:2 * k]]
        print(f"{cell}: {len(rows)} untraced runs, correct "
              f"{sum(r['correct'] for r in rows)}")
        for name in rows[0]["metrics"]:
            vals = [[r["metrics"][name]["value"] for r in s] for s in sets]
            meds = [statistics.median(v) for v in vals if len(v) >= 2]
            sps = [spread(v) for v in vals if len(v) >= 2]
            if not any(len(v) >= 3 for v in vals):
                continue
            bound = max(0.01, 5 * max(sps))
            tight = statistics.mean(spread(trimmed(v)) for v in vals
                                    if len(v) >= 3)
            print(f"  {name}: medians {meds} spreads "
                  f"{[round(s, 5) for s in sps]} -> bound {bound:.4f}; "
                  f"tightness reads {tight:.5f}, looseness {max(sps):.5f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
