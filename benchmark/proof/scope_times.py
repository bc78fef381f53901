#!/usr/bin/env python3
"""Device self time by the program's named scopes, from a trace a run left
behind (``run.py --trace 1 --keep-trace``): for PERF.md's breakdowns, where a
scope is read by hand and by no metric.

    python3 benchmark/proof/scope_times.py <cell> [steps]

Sums xprof's ``hlo_stats`` rows of the cell's kept trace under each ``mx.*``
scope (the innermost one an operation's ``op_name`` holds) and prints them
largest first, with the operations under no scope by name; ``steps`` divides
the sums (the traced segment's decode steps or batches).
"""
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(cell, steps=1.0):
    from benchmark import harness
    from benchmark.readers import program_spans

    path = harness.Tracer(False, cell).trace_file()
    if path is None:
        raise SystemExit("no kept trace for %s under .bench_out/trace" % cell)
    scopes, bare = {}, {}
    for r in program_spans.hlo_stats(path):
        found = re.findall(r"mx\.[a-z_.]+", r.get("tf_op_name") or "")
        us = r["total_self_time"] / steps
        if found:
            scopes[found[-1]] = scopes.get(found[-1], 0.0) + us
        else:
            key = "%s (%s)" % (r.get("hlo_op_name"), (r.get("tf_op_name") or "")[-60:])
            bare[key] = bare.get(key, 0.0) + us
    print("device self time, us%s:" % (" a step" if steps != 1.0 else ""))
    for name, us in sorted(scopes.items(), key=lambda t: -t[1]):
        print("  %-28s %12.1f" % (name, us))
    print("  %-28s %12.1f" % ("under no scope", sum(bare.values())))
    for name, us in sorted(bare.items(), key=lambda t: -t[1])[:12]:
        print("      %-90s %10.1f" % (name[:90], us))


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]) if len(sys.argv) > 2 else 1.0)
