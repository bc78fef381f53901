"""The share of the device's idle time in the traced segment that falls
under the program's ``args.span`` span, each idle instant going to the
innermost ``mx.*`` span open on any thread; ``args.span`` null: the share
under no span at all.  Percent of idle; nothing where the program opened no
``mx.*`` span or the device was never idle."""
from benchmark.readers import program_spans


def read(ctx, args):
    v = program_spans.for_context(ctx)
    if not v or not v["spans"]:
        return None
    shares = program_spans.idle_by_span(v)
    idle = sum(shares.values())
    if idle <= 0:
        return None
    return 100.0 * shares.get(args["span"], 0.0) / idle
