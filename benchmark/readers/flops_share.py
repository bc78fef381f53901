"""A whole step's share of the chip's peak: operations the model needs per
unit of work (``args.cost`` in ``benchmark/flops.py``) times the traced
segment's own rate, over chips times the bf16 peak.  Percent."""
from benchmark import flops


def read(ctx, args):
    seg, peaks = ctx["segment"], ctx["peaks"]
    if not seg or not peaks or not ctx["trace"]:
        return None
    work = seg["work"].get(args["work"], 0)
    if work <= 0 or seg["seconds"] <= 0:
        return None
    cell = ctx["cell"]
    per_unit = flops.COSTS[args["cost"]](cell.config, cell.traffic)
    return 100.0 * per_unit * work / seg["seconds"] / (cell.chips * peaks["bf16_flops_per_s"])
