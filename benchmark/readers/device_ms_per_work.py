"""Device busy time of the traced segment over its units of ``args.work``
(batches, steps), in milliseconds."""


def read(ctx, args):
    trace, seg = ctx["trace"], ctx["segment"]
    if not trace or not seg:
        return None
    work = seg["work"].get(args["work"], 0)
    if work <= 0:
        return None
    return 1000.0 * trace["busy_s"] / work
