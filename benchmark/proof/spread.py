#!/usr/bin/env python3
"""Spread of each metric over the result lines of one set of runs, as the
bound's rule reads it: the distance between the first and the third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python3 benchmark/proof/spread.py chiprun_out/set1_<cell>.jsonl [set2 ...]

Each file holds one result line per run (the last line ``run.py`` prints).
"""
import json
import statistics
import sys


def spread(values):
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths):
    for path in paths:
        rows = [json.loads(x) for x in open(path) if x.startswith('{"correct"')]
        names = sorted({n for r in rows for n in r["metrics"]})
        print("%s: %d runs, correct %s" % (path, len(rows),
                                          [r["correct"] for r in rows]))
        for n in names:
            v = [r["metrics"][n]["value"] for r in rows if n in r["metrics"]]
            if n == "setup_s":
                v = v[1:]                       # the first run compiles
            if len(v) >= 2:
                print("  %-24s median %.6g  spread %.3f %%  min %.6g max %.6g  n=%d"
                      % (n, statistics.median(v), 100 * spread(v), min(v), max(v), len(v)))
        for n in sorted({k for r in rows for k in r.get("compared", {})}):
            v = [r["compared"][n]["value"] for r in rows]
            print("  compared %-36s max %.3e (limit %g)"
                  % (n, max(v), rows[0]["compared"][n]["limit"]))


if __name__ == "__main__":
    main(sys.argv[1:])
