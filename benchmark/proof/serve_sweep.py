#!/usr/bin/env python3
"""The sweep that finds the highest rate a serving cell sustains, once, on
the chip: one process, one server, the cell's own mix at each of ``--rates``
for ``--seconds``, every window drained before the next.

    python3 benchmark/proof/serve_sweep.py opt-1.3b.serve-chat --rates 2,3,4,5,6

A rate is sustained when the backlog at the window's close is no longer than
the slots can hold and the drain is short; past the knee the time to first
token grows all through the window.  Not part of a run.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--rates", default="2,3,4,5,6")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2147489999)
    ap.add_argument("--wait", type=float, default=180.0,
                    help="how long past a window's close its requests are waited for")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmark import harness

    bench = os.path.join(ROOT, "benchmark", "rehearse", "BENCHMARK.json") \
        if args.rehearse else os.path.join(ROOT, "BENCHMARK.json")
    cell = harness.Cell(bench, args.cell)
    devices = harness.require_devices(cell.chips, args.rehearse)
    import mxnet_tpu  # noqa: F401
    import numpy as np

    runner = harness.load_module("runners", cell.traffic["runner"])
    run = runner.Run(cell, devices, args.seed, harness.Tracer(False, cell.name))
    t0 = time.perf_counter()
    run.setup()
    print("setup %.1f s" % (time.perf_counter() - t0), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, "sweep_%s.jsonl" % args.cell), "a")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        run.mix = dict(cell.traffic, rate_per_s=rate, wait_after_s=args.wait)
        run.seed = args.seed + i
        run.profiler.generate_reset()
        got = run.window(args.seconds)
        done = [s for s in run.served if s["tokens"] is not None]
        if not done:
            print("rate %g: nothing finished" % rate, flush=True)
            break
        ttft = np.array([s["stamps"][0] - s["due"] for s in done]) * 1e3
        order = np.argsort([s["due"] for s in done])
        half = len(order) // 2
        last = max(s["stamps"][-1] for s in done)
        tokens = sum(len(s["tokens"]) for s in done)
        rec = {"rate_per_s": rate, "requests": run.attempted, "failed": run.failed,
               "ttft_p50_ms": float(np.percentile(ttft, 50)),
               "ttft_p95_ms": got["serve_ttft_p95_ms"],
               "ttft_p50_first_half_ms": float(np.percentile(ttft[order[:half]], 50)),
               "ttft_p50_second_half_ms": float(np.percentile(ttft[order[half:]], 50)),
               "itl_p95_ms": got["serve_itl_p95_ms"],
               "drain_s": last - (got["_window_start"] + got["_elapsed_s"]),
               "tokens_per_s": tokens / (last - got["_window_start"]),
               "counters": got["_work"],
               "device": devices[0].device_kind}
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()
    run.release()


if __name__ == "__main__":
    main()
