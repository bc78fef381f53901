#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: it loads the cell, makes weights and inputs from the seed,
warms up every shape the window uses (all of that is ``setup_s``), measures
for ``--seconds``, reads the peak memory, frees the program's state, compares
what the timed path produced with the plain reference, and prints the result
as the last line of standard output.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces a few seconds of the window with
``jax.profiler`` and reports its per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result: it never falls back to the CPU.  ``--rehearse``
is the one exception, and it is a rehearsal, not a measurement: it runs the
tiny cells of ``benchmark/rehearse/BENCHMARK.json`` on whatever JAX finds and
writes "not measured" where a device metric would stand.
"""
import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the trace under .bench_out/ for a look by hand")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny cells from benchmark/rehearse, any platform; "
                         "measures nothing")
    return ap.parse_args(argv)


def run_cell(args, process_start):
    from benchmark import harness

    bench_path = os.path.join(HERE, "rehearse", "BENCHMARK.json") if args.rehearse \
        else os.path.join(ROOT, "BENCHMARK.json")
    cell = harness.Cell(bench_path, args.workload)
    seconds = float(args.seconds if args.seconds is not None
                    else cell.bench["run_seconds"])

    devices = harness.require_devices(cell.chips, args.rehearse)
    on_tpu = devices[0].platform == "tpu"
    import mxnet_tpu  # noqa: F401  places the persistent compile cache

    counter = harness.CompileCounter()
    tracer = harness.Tracer(args.trace, cell.name,
                            after_s=cell.traffic.get("trace_after_s", 2.0),
                            seconds=cell.traffic.get("trace_seconds", 3.0))
    runner = harness.load_module("runners", cell.traffic["runner"])
    run = runner.Run(cell, devices, args.seed, tracer)

    tracer.on_window = counter.mark_window
    run.setup()
    measured = run.window(seconds)
    # a runner whose set-up ends inside its own call (``fit``'s first epoch)
    # says where the window began
    setup_s = measured["_window_start"] - process_start
    compiled_setup = counter.at_window
    compiled_window = counter.from_scratch() - compiled_setup
    peak = harness.memory_peak_bytes(devices)
    print("setup_s=%.3f window_s=%.3f programs_compiled_in_setup=%d "
          "programs_compiled_in_window=%d" % (setup_s, measured["_elapsed_s"],
                                              compiled_setup, compiled_window),
          flush=True)

    run.release()
    checks, attempted, failed = run.check()
    correct = harness.judge(checks) and failed == 0

    measured["setup_s"] = setup_s
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": int(attempted), "failed": int(failed),
              "metrics": {}, "device": device}
    if args.trace:
        from benchmark import trace_reduce

        summary = None
        if tracer.trace_file() and on_tpu:
            summary = trace_reduce.reduce(tracer.trace_file(), len(devices))
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            result["breakdown"] = {"device_ops": summary["top_ops"],
                                   "idle_gaps": summary["top_gaps"]}
        context = {"cell": cell, "trace": summary, "segment": tracer.segment(),
                   "counters": getattr(run, "counters", dict)(),
                   "peaks": harness.peaks_for(devices[0]) if on_tpu else None}
        result["metrics"] = harness.read_per_layer(cell, context)
        if not args.keep_trace:
            tracer.cleanup()
        listed = cell.per_layer()
    else:
        listed = cell.end_to_end()
        for m in listed:
            if m["name"] in measured:
                result["metrics"][m["name"]] = {"value": float(measured[m["name"]]),
                                                "unit": m["unit"]}
    if not on_tpu:
        result["rehearsal"] = True
        for m in listed:
            print("%s: not measured (platform %s)" % (m["name"], device["platform"]))
        result["metrics"] = {}
    result["compiled_in_window"] = compiled_window
    harness.print_result(result, checks)
    return 0


def main(argv=None):
    args = parse(sys.argv[1:] if argv is None else argv)
    return run_cell(args, _PROCESS_START)


if __name__ == "__main__":
    sys.exit(main())
