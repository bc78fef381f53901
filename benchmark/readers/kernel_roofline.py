"""A kernel's share of its roofline: the least time the chip could take for
the calls the traced segment made (the larger of operations over peak FLOP/s
and bytes over peak bytes/s, from ``args.cost`` in ``benchmark/flops.py``)
over the device time of the operations whose names match ``args.match``.
``args.calls`` says how many calls a unit of ``args.work`` makes, as a key of
the configuration's ``program`` group.  Percent; nothing where no such
operation ran."""
from benchmark import flops
from benchmark.trace_reduce import matching


def read(ctx, args):
    trace, seg, peaks = ctx["trace"], ctx["segment"], ctx["peaks"]
    if not trace or not seg or not peaks:
        return None
    seconds, count = matching(trace["ops"], args["match"])
    if count == 0 or seconds <= 0:
        return None
    cell = ctx["cell"]
    cost = flops.COSTS[args["cost"]](cell.config, cell.traffic)
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    calls = seg["work"].get(args["work"], 0) * cell.config["program"][args["calls"]]
    if calls <= 0:
        return None
    return 100.0 * calls * least / (seconds * cell.chips)
