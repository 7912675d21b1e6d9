#!/usr/bin/env python3
"""Look at a profiler trace by hand: print the planes and lines of a
saved ``.xplane.pb``, the device operations that took most time with
their stats, and a sample of events, as JSON.

    python3 benchmarks/chip/run.py --workload <cell> --seed 5 \
        --seconds 10 --trace 1 --keep trace.xplane.pb
    python3 benchmarks/chip/tools/dump_trace.py trace.xplane.pb
"""
import argparse
import collections
import json
import sys


def describe(path: str, top: int = 40, sample: int = 25) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        p = {"plane": plane.name, "stats": [list(map(str, s))
                                            for s in plane.stats][:20],
             "lines": []}
        for line in plane.lines:
            events = list(line.events)
            tot = collections.Counter()
            cnt = collections.Counter()
            stats_of = {}
            for e in events:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
                stats_of.setdefault(e.name, [list(map(str, s))
                                             for s in e.stats])
            lo = min((e.start_ns for e in events), default=0)
            hi = max((e.start_ns + e.duration_ns for e in events), default=0)
            p["lines"].append({
                "line": line.name, "events": len(events),
                "span_ns": [lo, hi],
                "top": [[n, tot[n], cnt[n], stats_of[n]]
                        for n, _ in tot.most_common(top)],
                "first": [[e.name, e.start_ns, e.duration_ns]
                          for e in events[:sample]]})
        out.append(p)
    return {"path": path, "planes": out}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane", help="a trace kept by run.py --keep")
    args = ap.parse_args(argv)
    print(json.dumps(describe(args.xplane)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
