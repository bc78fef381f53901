"""1 - (union of the device's operation intervals) / traced segment.  Percent."""


def read(ctx, args):
    trace = ctx["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
