#!/usr/bin/env python3
"""Readings that a serving cell's limit is set from, on the chip at the
cell's own size and load: for each seed a server of its own (the weights are
the seed's), a short window, and then, with the server's state freed, the
plain reference over the sample (the lower reading) and the control, the
reference in the nearest precision below the configuration's, judged at the
same positions (the upper reading).

    python3 benchmark/proof/serve_readings.py opt-1.3b.serve-chat --seeds 3

One JSON line per seed, also appended to ``chiprun_out/readings_<cell>.jsonl``.
Not part of a run.
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
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmark import harness
    from benchmark.reference import precision

    bench = os.path.join(ROOT, "benchmark", "rehearse", "BENCHMARK.json") \
        if args.rehearse else os.path.join(ROOT, "BENCHMARK.json")
    cell = harness.Cell(bench, args.cell)
    devices = harness.require_devices(cell.chips, args.rehearse)
    import mxnet_tpu  # noqa: F401

    runner = harness.load_module("runners", cell.traffic["runner"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, "readings_%s.jsonl" % args.cell), "a")
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        run = runner.Run(cell, devices, seed, harness.Tracer(False, cell.name))
        run.setup()
        got = run.window(args.seconds)
        run.release()
        picked = run.sample()
        below = precision.CONTROL_BELOW[run.compute_dtype]
        import numpy as np

        exact = np.concatenate(run.reference_gaps(picked))
        control = np.concatenate(run.reference_gaps(picked,
                                                    quant=precision.CONTROLS[below]))
        rec = {"cell": args.cell, "seed": seed, "device": devices[0].device_kind,
               "seconds": round(time.perf_counter() - t0, 1),
               "requests": run.attempted, "failed": run.failed,
               "compared_tokens": len(exact),
               "program": {**dict(run.compare(exact)), "off_the_best": int((exact > 0).sum()),
                           "p99": float(np.percentile(exact, 99))},
               "control_" + below: {**dict(run.compare(control)),
                                    "off_the_best": int((control > 0).sum()),
                                    "p99": float(np.percentile(control, 99))},
               "ttft_p95_ms": got["serve_ttft_p95_ms"], "itl_p95_ms": got["serve_itl_p95_ms"]}
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()
    out.close()


if __name__ == "__main__":
    main()
