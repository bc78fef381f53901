#!/usr/bin/env python
"""Show what each IR pass does to a model graph (ISSUE 13).

Builds a model symbol, runs the requested pass pipeline ONE PASS AT A
TIME, and prints the before/after per pass: node counts, the per-op
histogram delta, and every rule application in order (the pass
provenance). The last line is a single JSON record (the bench.py
convention) so tooling can diff pass behavior across rounds.

    python tools/dump_graph.py --model resnet --layers 50 --passes fusion
    python tools/dump_graph.py --model resnet-basic --tiny --passes residual
    python tools/dump_graph.py --model mlp --passes fusion,residual --json

``--shapes data:2,3,64,64`` arms the PassManager's output-shape guard
(a rewrite that changes output shapes fails loudly with PassError).
"""
import argparse
import json
import os
import sys
from collections import Counter

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build_symbol(args):
    from mxnet_tpu.models.resnet import get_symbol, resnet

    if args.model == "resnet":
        if args.tiny:
            return resnet(units=[2, 1], num_stages=2,
                          filter_list=[8, 16, 32],
                          num_classes=args.classes,
                          image_shape=(3, 64, 64), bottle_neck=True)
        return get_symbol(num_classes=args.classes,
                          num_layers=args.layers,
                          image_shape=tuple(args.image_shape))
    if args.model == "resnet-basic":
        if args.tiny:
            return resnet(units=[2, 1], num_stages=2,
                          filter_list=[8, 16, 32],
                          num_classes=args.classes,
                          image_shape=(3, 64, 64), bottle_neck=False)
        return get_symbol(num_classes=args.classes, num_layers=18,
                          image_shape=tuple(args.image_shape))
    if args.model == "mlp":
        from tools.bench_serve import build_model

        sym, _ = build_model(128, 256, 4, args.classes)
        return sym
    raise SystemExit("unknown --model %r" % args.model)


def op_histogram(symbol):
    return Counter(n.op.name for n in symbol._topo()
                   if not n.is_variable())


def parse_shapes(spec):
    shapes = {}
    for part in (spec or "").split(";"):
        part = part.strip()
        if not part:
            continue
        name, dims = part.split(":")
        shapes[name] = tuple(int(d) for d in dims.split(","))
    return shapes


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="resnet",
                    choices=("resnet", "resnet-basic", "mlp"))
    ap.add_argument("--layers", type=int, default=50)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--image-shape", type=int, nargs=3,
                    default=(3, 224, 224))
    ap.add_argument("--tiny", action="store_true",
                    help="2-stage tiny stack (smoke tests)")
    ap.add_argument("--passes", default=None,
                    help="comma list of registered passes (default: "
                         "the MXNET_IR_PASSES knob)")
    ap.add_argument("--shapes", default=None,
                    help='arm the shape guard: "data:2,3,64,64[;...]"')
    ap.add_argument("--json", action="store_true",
                    help="only the JSON record, no per-pass text")
    args = ap.parse_args(argv)

    from mxnet_tpu import ir

    symbol = build_symbol(args)
    names = args.passes.split(",") if args.passes else None
    manager = ir.PassManager(names, data_shapes=parse_shapes(args.shapes))

    record = {"model": args.model, "passes": [], "tiny": args.tiny}
    for name in manager.names:
        before = op_histogram(symbol)
        single = ir.PassManager((name,),
                                data_shapes=manager.data_shapes)
        symbol, provs = single.apply(symbol)
        prov = provs[0]
        after = op_histogram(symbol)
        delta = {op: after.get(op, 0) - before.get(op, 0)
                 for op in sorted(set(before) | set(after))
                 if after.get(op, 0) != before.get(op, 0)}
        entry = dict(prov, op_delta=delta)
        record["passes"].append(entry)
        if not args.json:
            print("== pass %-12s nodes %d -> %d, %d rewrites"
                  % (name, prov["nodes_before"], prov["nodes_after"],
                     prov["rewrites"]))
            for op, d in sorted(delta.items()):
                print("   %-24s %+d" % (op, d))
            applied = Counter(prov["applied"])
            for rule, count in sorted(applied.items()):
                print("   rule %-28s x%d" % (rule, count))
    record["final_ops"] = dict(op_histogram(symbol))
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
