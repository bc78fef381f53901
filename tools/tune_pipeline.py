#!/usr/bin/env python
"""Training-pipeline sweep driver (ISSUE 19).

Runs :func:`mxnet_tpu.tune.sweep_train_pipelines` over the Symbol-level
bench transformer: every remat x layout candidate is compiled once,
featurized from the compiler's own memory/cost analyses, ranked by the
learned cost model (abstain -> exhaustive), timed, and the winner
committed to the on-disk schedule table under the graph's structural
fingerprint. Subsequent ``TrainStep``-building jobs consult the entry
via :func:`mxnet_tpu.tune.pipeline_for`.

Run it as its own command: it holds the chip while it sweeps. The last
stdout line is a JSON report (the bench.py convention).

    python tools/tune_pipeline.py --cpu --steps 3
    python tools/tune_pipeline.py --batch 16 --seq-len 128 --d-model 256
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (plumbing validation "
                         "off-TPU; winners commit under backend=cpu)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--n-heads", type=int, default=4)
    ap.add_argument("--n-layers", type=int, default=2)
    ap.add_argument("--d-ff", type=int, default=512)
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--steps", type=int, default=3,
                    help="timed steps per surviving candidate")
    ap.add_argument("--table", default=None,
                    help="schedule-table path (default: the shared "
                         "on-disk table)")
    ap.add_argument("--ranked", dest="ranked", action="store_true",
                    default=None,
                    help="force cost-model ranked sweep")
    ap.add_argument("--no-ranked", dest="ranked", action="store_false",
                    help="force exhaustive sweep")
    args = ap.parse_args(argv)

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from mxnet_tpu.models import bench_transformer
    from mxnet_tpu.parallel.spmd import functional_optimizer
    from mxnet_tpu.tune import sweep_train_pipelines
    from mxnet_tpu.tune.table import ScheduleTable, get_table

    sym = bench_transformer.get_symbol(
        num_classes=args.classes, seq_len=args.seq_len,
        d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff)
    rng = np.random.RandomState(0)
    batch = {
        "data": rng.randn(args.batch, args.seq_len,
                          args.d_model).astype(np.float32),
        "softmax_label": rng.randint(
            0, args.classes, (args.batch,)).astype(np.float32),
    }
    table = (ScheduleTable(args.table) if args.table else get_table())
    report = sweep_train_pipelines(
        sym, functional_optimizer("sgd", learning_rate=0.1),
        batch, table=table, ranked=args.ranked, steps=args.steps)
    w = report["winner"]
    print("winner: remat=%s layout=%s  %.3f ms/step (%.2fx vs default), "
          "peak %.1f MB  [%s]"
          % (w["choice"]["remat"], w["choice"]["layout"],
             w["ms_per_iter"], w["speedup_vs_default"],
             w["peak_bytes"] / 1e6, report["ranker"]["mode"]))
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
