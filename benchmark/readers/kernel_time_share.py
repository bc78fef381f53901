"""Device time of the operations whose names match ``args.match`` over the
device's busy time in the traced segment.  Percent; nothing where no such
operation ran."""
from benchmark.trace_reduce import matching


def read(ctx, args):
    trace = ctx["trace"]
    if not trace or trace["busy_s"] <= 0:
        return None
    seconds, count = matching(trace["ops"], args["match"])
    if count == 0:
        return None
    return 100.0 * seconds / trace["busy_s"]
