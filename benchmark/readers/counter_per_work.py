"""A counter of the program (``args.counter``, as the runner's ``counters``
reports its growth over the traced segment) over the segment's units of
``args.work``, times ``args.scale``.  Host-side counts and host-clock sums:
the metric's name says so."""


def read(ctx, args):
    seg = ctx["segment"]
    if not seg:
        return None
    work = seg["work"].get(args["work"], 0)
    value = seg["work"].get(args["counter"])
    if work <= 0 or value is None:
        return None
    return float(args.get("scale", 1.0)) * value / work
