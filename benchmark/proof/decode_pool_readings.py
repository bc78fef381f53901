#!/usr/bin/env python3
"""Readings that the decode-pool cell's limit is set from, on the chip at the
cell's own size: for each seed a server of its own (the weights are the
seed's), a short window, and then, with the server's state freed, the plain
reference over the sampled streams (the lower reading) and the control, the
reference with every product's operands rounded to int8, judged at the same
positions (the upper reading).  Both go through ``harness.judge`` against the
cell's limit, as a run's own numbers do.

    python3 benchmark/proof/decode_pool_readings.py --seeds 2 --first-seed 3600001000

Two further readings say where the program's own gap comes from:

``--witness``  the same weights, widths and decode program with every context
    under ``index_topk`` (16 equal prompts of 1536 tokens, 384 served each,
    all 16 slots compared): every cached key is kept, so no selection can
    flip, and what is left is the rounding of the products.
``--plant selection_dropped``  the cell as it is with the indexer's decode
    scores zeroed, so that the kept keys are the earliest positions and not
    the indexer's choice: a broken selection has to read over the limit.

One JSON line per seed, also appended to
``chiprun_out/readings_glm-5.decode-pool-16k.jsonl``.  Not part of a run.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
CELL = "glm-5.decode-pool-16k"
WITNESS = {"prompt_tokens": {"median": 1536, "sigma": 0.0, "min": 1536, "max": 1536},
           "answer_tokens": 384, "check_tokens": 16 * 384}


def zeroed_decode_scores(scores):
    """``mla_moe._index_scores`` with every cached key scoring alike in decode
    (keys (S, K, dim)), so the kept ones are the earliest positions and not
    the indexer's choice; prefill's scores stay."""
    import jax.numpy as jnp

    return lambda q_i, w, k_i: (jnp.zeros_like(scores(q_i, w, k_i)) if k_i.ndim == 3
                                else scores(q_i, w, k_i))


def readings(run, seconds, control=True):
    """A set-up ``run`` through a window of ``seconds``, its server freed,
    then the sampled streams through the reference and, with ``control``,
    through the reference in the precision below the cell's: the window's
    result, the program's gaps and the control's (or None), and the name of
    the control's precision."""
    import numpy as np

    from benchmark.reference import precision

    got = run.window(seconds)
    run.release()
    picked = run.sample()
    below = precision.CONTROL_BELOW[run.compute_dtype]
    exact = np.concatenate(run.reference_gaps(picked))
    low = np.concatenate(run.reference_gaps(picked, quant=precision.CONTROLS[below])) \
        if control else None
    return got, exact, low, below


def judged(run, gaps):
    """``harness.judge`` of ``gaps`` against the cell's limits."""
    from benchmark import harness

    return harness.judge([{"name": n, "value": v, "limit": run.cell.limits[n]}
                          for n, v in run.compare(gaps) if n in run.cell.limits])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--first-seed", type=int, default=3600001000)
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--plant", choices=["selection_dropped"])
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args()

    import numpy as np

    from benchmark import harness

    cell = harness.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    if args.witness:
        cell.traffic = dict(cell.traffic, **WITNESS)
    devices = harness.require_devices(cell.chips, False)
    import mxnet_tpu  # noqa: F401

    if args.plant:
        from mxnet_tpu.models import mla_moe

        mla_moe._index_scores = zeroed_decode_scores(mla_moe._index_scores)
    runner = harness.load_module("runners", cell.traffic["runner"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "readings_%s.jsonl" % CELL), "a")
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        run = runner.Run(cell, devices, seed, harness.Tracer(False, cell.name))
        run.setup()
        got, exact, low, below = readings(run, args.seconds, not args.no_control)

        def reading(gaps):
            return {**dict(run.compare(gaps)), "off_the_best": int((gaps > 0).sum()),
                    "p99": float(np.percentile(gaps, 99)), "judged": judged(run, gaps)}

        line = {"cell": CELL, "seed": seed, "device": devices[0].device_kind,
                "what": "witness: all keys kept" if args.witness else args.plant or "cell",
                "seconds": round(time.perf_counter() - t0, 1),
                "streams": run.attempted, "failed": run.failed,
                "contexts": [min(len(s["prompt"]) for s in run.served),
                             max(len(s["prompt"]) + len(s["stamps"]) for s in run.served)],
                "compared_tokens": len(exact), "program": reading(exact),
                "itl_p95_ms": got["serve_itl_p95_ms"]}
        if low is not None:
            line["control_" + below] = reading(low)
        line = json.dumps(line)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()
    out.close()


if __name__ == "__main__":
    main()
