#!/usr/bin/env python3
"""Readings that the limits of a training cell are set from, on the chip at
the cell's own size, many seeds in one process.

    python3 benchmark/proof/train_readings.py <cell> --seeds 12 --control 3

For each seed: the program's first steps against the plain reference (the
lower reading); for the first ``--control`` seeds also the control (the
reference with int8 operands in its place) and the planted half-batch fault
(the reference with the second half of the rows left out, the mean taken over
the rest), each against the reference.  One JSON line per reading, also
appended to ``chiprun_out/readings_<cell>.jsonl``.  Not part of a run.
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
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--leaves", action="store_true",
                    help="also write every leaf's two norms")
    ap.add_argument("--kinds", default="control_int8,fault_half_batch")
    ap.add_argument("--witness-dtype", default=None,
                    help="also run the program of the first --control seeds "
                         "computing in this type (a fit cell's float32 "
                         "witness that a gap is rounding and no fault)")
    args = ap.parse_args()

    from benchmark import harness
    from benchmark.reference import precision as ref

    bench = os.path.join(ROOT, "benchmark", "rehearse", "BENCHMARK.json") \
        if args.rehearse else os.path.join(ROOT, "BENCHMARK.json")
    cell = harness.Cell(bench, args.cell)
    devices = harness.require_devices(cell.chips, args.rehearse)
    import mxnet_tpu  # noqa: F401

    runner = harness.load_module("runners", cell.traffic["runner"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, "readings_%s.jsonl" % args.cell), "a")

    def emit(kind, seed, got, want, t0):
        rec = {"cell": args.cell, "kind": kind, "seed": seed,
               "seconds": round(time.perf_counter() - t0, 2),
               "device": devices[0].device_kind,
               **{n: v for n, v in run.compare(got, want)}}
        if args.leaves:
            rec["got"], rec["want"] = got, want
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    kinds = [k for k in args.kinds.split(",") if k]
    half = list(range(int(cell.traffic["batch"]) // 2))
    plant = {"control_int8": {"quant": ref.CONTROLS["int8"]},
             "control_fp8": {"quant": ref.CONTROLS["fp8"]},
             "control_bf16": {"quant": ref.CONTROLS["bf16"]},
             "fault_half_batch": {"rows": half}}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        run = runner.Run(cell, devices, seed, harness.Tracer(False, cell.name))
        t0 = time.perf_counter()
        run.setup()
        run.window(0.5)
        run.release()
        got = run.read
        want = run.reference_readings()
        emit("program", seed, got, want, t0)
        if i < args.control:
            for kind in kinds:
                t0 = time.perf_counter()
                try:
                    emit(kind, seed, run.reference_readings(**plant[kind]), want, t0)
                except Exception as e:        # a control that crashes has failed
                    print("%s seed %d: %r" % (kind, seed, e), flush=True)
            if args.witness_dtype:
                import jax

                t0 = time.perf_counter()
                # a float32 product on the TPU is one bfloat16 pass unless
                # the precision is raised, so the witness raises it
                with jax.default_matmul_precision("highest"):
                    run = runner.Run(cell, devices, seed,
                                     harness.Tracer(False, cell.name))
                    run.compute_dtype = args.witness_dtype
                    run.setup()
                    run.window(0.5)
                    run.release()
                emit("program_%s_highest" % args.witness_dtype, seed, run.read, want, t0)
    out.close()


if __name__ == "__main__":
    main()
