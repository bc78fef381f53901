"""A number the runner took over the whole window (``args.key`` of its
``counters()``), times ``args.scale``: a host-clock statistic that stands
beside an end-to-end metric without a bound of its own.  Nothing where the
runner has none."""


def read(ctx, args):
    value = (ctx["counters"] or {}).get(args["key"])
    if value is None:
        return None
    return float(args.get("scale", 1.0)) * value
