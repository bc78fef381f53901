"""Device time of the compiled programs whose names match ``args.match``
(``jit_<function>`` on the trace's ``XLA Modules`` line) over the number of
times they ran in the traced segment, in milliseconds.  Nothing where none
ran."""
from benchmark.trace_reduce import matching


def read(ctx, args):
    trace = ctx["trace"]
    if not trace:
        return None
    seconds, runs = matching(trace["modules"], args["match"])
    if runs == 0:
        return None
    return 1000.0 * seconds / runs
