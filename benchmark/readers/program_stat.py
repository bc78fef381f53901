"""``args.key`` of ``mxnet_tpu.profiler.<args.family>_stats()`` read after
the window, times ``args.scale``: a count or a host-clock sum the program
keeps at the boundary where the work happens (the runners hand on only the
counters they list, so the reader asks the program).  Nothing where the
program keeps no such number."""


def read(ctx, args):
    from mxnet_tpu import profiler

    stats = getattr(profiler, args["family"] + "_stats", None)
    value = stats().get(args["key"]) if stats else None
    if value is None:
        return None
    return float(args.get("scale", 1.0)) * value
