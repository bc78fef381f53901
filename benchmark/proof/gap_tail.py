#!/usr/bin/env python3
"""What a token's gap holds, from a trace a serving run left behind
(``run.py --trace 1 --keep-trace``): for PERF.md's decomposition of
``serve_itl_p95_ms``, read by hand beside the metrics of
``benchmark/readers/decode_read_tail.py``.

    python3 benchmark/proof/gap_tail.py <cell or .xplane.pb> [widest]
    python3 benchmark/proof/gap_tail.py --window <cell> <seed> [seconds] [BENCHMARK.json]

Prints, for the traced segment: the host's period between the ends of
consecutive ``mx.serve.decode.read`` spans (a token's gap as the broker sees
it), the device's period between the ends of the decode runs those reads
waited for, the lag from a run's end to its read's end, Python's collections
(``mx.host.gc``, any thread), and for the ``widest`` host periods (20) the
time each ``mx.*`` span on the broker's thread held of it.  Exit 2 where the
trace holds no read span.

``--window`` runs the cell untraced through its own runner, as ``run.py
--trace 0`` does but without the reference, and prints its
``serve_itl_p95_ms`` and the gaps by 5 s of the window (each gap at its later
token's stamp): whether a gap's tail is the whole window's or one part's.
"""
import gc
import os
import sys
import time
import warnings

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _ms(ns):
    return ns / 1e6


def _quantiles(label, ns):
    if not ns:
        print("%-34s none" % label)
        return
    p = np.percentile(ns, [50, 95, 99])
    print("%-34s n %5d  p50 %8.3f  p95 %8.3f  p99 %8.3f  max %8.3f  mean %8.3f ms"
          % (label, len(ns), _ms(p[0]), _ms(p[1]), _ms(p[2]), _ms(max(ns)), _ms(np.mean(ns))))


def collections(data, lo, hi):
    """[(start, end, generation, collected)] of the ``mx.host.gc`` events
    that lie in the segment, on any host line."""
    out = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name != "mx.host.gc":
                    continue
                a = int(e.start_ns)
                b = a + int(e.duration_ns)
                if b > lo and a < hi:
                    with warnings.catch_warnings():   # jaxlib's stats type
                        warnings.simplefilter("ignore", DeprecationWarning)
                        stats = {k: x for k, x in e.stats}
                    out.append((a, b, stats.get("generation"), stats.get("collected")))
    return sorted(out)


def around(data, a, b, least_ns=1_000_000):
    """Every host event of at least ``least_ns`` that overlaps ``(a, b)``, on
    any line, as (line, start, end, name): what else the process did."""
    out = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                c = int(e.start_ns)
                d = c + int(e.duration_ns)
                if d > a and c < b and d - c >= least_ns:
                    out.append((line.name, c, d, e.name))
    return sorted(out, key=lambda t: t[1])


def main(where, widest=20):
    from benchmark import harness, trace_reduce
    from benchmark.readers import decode_read_tail, program_spans

    path = where if where.endswith(".pb") else harness.Tracer(False, where).trace_file()
    if path is None:
        raise SystemExit("no kept trace for %s under .bench_out/trace" % where)
    data = trace_reduce._load(path)
    v = program_spans.view(data)
    if not v["segment"]:
        raise SystemExit("no segment in %s" % path)
    lo, hi = v["segment"]
    reads = sorted((b, a, t) for a, b, n, t, w in v["spans"]
                   if n == decode_read_tail.READ and b - a == w)
    print("trace %s\nsegment %.3f s, %d read spans wholly inside" % (path, (hi - lo) / 1e9, len(reads)))
    if not reads:
        # a program without read spans (the parent): the device's idle gaps of
        # 20 ms or more and what the host did in them
        for a, b in [g for g in (v["idle"][0] if v["idle"] else []) if g[1] - g[0] >= 20_000_000]:
            print("device idle %.3f to %.3f ms (%.3f ms); host events of 1 ms or more in it:"
                  % (_ms(a - lo), _ms(b - lo), _ms(b - a)))
            for line, c, d, n in around(data, a, b):
                print("    %-24s %9.3f to %9.3f ms  %s" % (line[:24], _ms(c - lo), _ms(d - lo), n[:90]))
        sys.exit(2)
    runs = [(a, b) for a, b, n in v["modules"] if n == decode_read_tail.DECODE and b > lo and a < hi]
    _quantiles("jit_decode run on the device", [b - a for a, b in runs])
    host = [b2 - b1 for (b1, _a, _t), (b2, _a2, _t2) in zip(reads, reads[1:])]
    _quantiles("host period (read end to read end)", host)
    _quantiles("device period (decode_read_tail)", decode_read_tail.values(v, "period"))
    _quantiles("lag (run end to read end)", decode_read_tail.values(v, "lag"))
    _quantiles("read span itself", [b - a for b, a, _t in reads])

    gcs = collections(data, lo, hi)
    gens = {}
    for a, b, g, _c in gcs:
        gens.setdefault(g, []).append(b - a)
    print("collections in the segment: %d (%.1f a second), %.3f ms in all"
          % (len(gcs), len(gcs) / ((hi - lo) / 1e9), _ms(sum(b - a for a, b, *_ in gcs))))
    for g in sorted(gens, key=str):
        print("  generation %s: %d, mean %.3f ms, max %.3f ms"
              % (g, len(gens[g]), _ms(np.mean(gens[g])), _ms(max(gens[g]))))

    broker = max({t for _b, _a, t in reads}, key=lambda t: sum(1 for r in reads if r[2] == t))
    ends = sorted(b for a, b in runs)
    pairs = dict(decode_read_tail.pairs(v))
    order = sorted(range(len(host)), key=lambda i: -host[i])[:widest]
    print("the %d widest host periods: ms, the device's period and the read's lag,"
          " then ms of each mx.* span on the broker's thread inside it" % len(order))
    for i in sorted(order):
        a, b = reads[i][0], reads[i + 1][0]
        e1, e2 = pairs.get(a), pairs.get(b)
        dev = "%.3f" % _ms(e2 - e1) if e1 and e2 else "-"
        lag = "%.3f" % _ms(b - e2) if e2 else "-"
        held = {}
        for c, d, n, t, _w in v["spans"]:
            if (t == broker or n == "mx.host.gc") and d > a and c < b:
                key = n if t == broker else n + " (other thread)"
                held[key] = held.get(key, 0) + min(b, d) - max(a, c)
        idle = sum(min(b, d) - max(a, c) for c, d in v["idle"][0] if d > a and c < b) if v["idle"] else 0
        busy_runs = [x for x in ends if a < x <= b]
        print("  at %8.3f ms: %7.3f  device %s  lag %s  idle %.3f  runs ended %d | %s"
              % (_ms(a - lo), _ms(b - a), dev, lag, _ms(idle), len(busy_runs),
                 ", ".join("%s %.3f" % (n, _ms(ns)) for n, ns in sorted(held.items(), key=lambda t: -t[1]))))
    # the widest periods whose read waited long after its run had ended:
    # every host event of a millisecond or more in them, on any thread
    late = [i for i in order if pairs.get(reads[i + 1][0]) and
            reads[i + 1][0] - pairs[reads[i + 1][0]] > 5 * np.median(decode_read_tail.values(v, "lag"))]
    for i in sorted(late)[:3]:
        a, b = reads[i][0], reads[i + 1][0]
        print("host events of 1 ms or more in the period at %.3f ms (read %d's lag %.3f ms):"
              % (_ms(a - lo), i + 1, _ms(b - pairs[b])))
        for line, c, d, n in around(data, a, b):
            print("    %-24s %9.3f to %9.3f ms  %s" % (line[:24], _ms(c - lo), _ms(d - lo), n[:90]))


def window(cell_name, seed, seconds=None, bench=None):
    from benchmark import harness

    cell = harness.Cell(bench or os.path.join(ROOT, "BENCHMARK.json"), cell_name)
    devices = harness.require_devices(cell.chips, bool(bench))
    import mxnet_tpu  # noqa: F401  places the persistent compile cache

    runner = harness.load_module("runners", cell.traffic["runner"])
    run = runner.Run(cell, devices, int(seed), harness.Tracer(False, cell.name))
    run.setup()
    collected, opened = {}, []

    def count(phase, info):
        """Python's collections in the window, by generation, with their time."""
        if phase == "start":
            opened.append(time.perf_counter())
        elif opened:
            n, t = collected.get(info["generation"], (0, 0.0))
            collected[info["generation"]] = (n + 1, t + time.perf_counter() - opened.pop())

    gc.callbacks.append(count)
    measured = run.window(float(seconds or cell.bench["run_seconds"]))
    gc.callbacks.remove(count)
    t0, t1 = measured["_window_start"], measured["_window_start"] + measured["_elapsed_s"]
    inside = "serve_decode_pool" in cell.traffic["runner"]   # gaps stamped in the window only
    by = {}
    for s in run.served:
        st = [t for t in s["stamps"] if t0 <= t <= t1] if inside else \
            (s["stamps"] if s["tokens"] is not None else [])
        for a, b in zip(st, st[1:]):
            by.setdefault(int((b - t0) // 5), []).append(1e9 * (b - a))
    print("serve_itl_p95_ms %.4f (%s, seed %s, %d streams)"
          % (measured["serve_itl_p95_ms"], cell_name, seed, len(run.served)))
    for k in sorted(by):
        _quantiles("gaps stamped at %3d-%3d s" % (5 * k, 5 * k + 5), by[k])
    print("collections in the window (%.1f s): %s" % (
        measured["_elapsed_s"], ", ".join("generation %d: %d, %.3f ms" % (g, n, 1e3 * t)
                                         for g, (n, t) in sorted(collected.items())) or "none"))
    run.release()


if __name__ == "__main__":
    if sys.argv[1] == "--window":
        window(*sys.argv[2:6])
    else:
        main(sys.argv[1], *(int(x) for x in sys.argv[2:3]))
