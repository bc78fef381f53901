"""The tail of a token's gap on the device's clock, from the broker's
``mx.serve.decode.read`` spans (the host waiting for the ids a decode step
chose) and the runs of the decode program (``jit_decode`` on the first
chip's ``XLA Modules`` line) in the same trace.

Each read span wholly inside the traced segment is paired with the last run
that ended before the span did: the step whose ids it waited for.  With
``args.what``:

- ``"lag"``: the span's end less that run's end, the time from the step's
  end on the device to its ids on the host;
- ``"period"``: for consecutive reads, the time between the ends of the
  runs they were paired with, the device's own period between two tokens.

The ``args.q``-th percentile of those, in milliseconds.  Nothing where the
program opens no read span (the parent of the PR that brought it), the trace
has no device plane, or fewer than ``LEAST`` values are there.
"""
import bisect

import numpy as np

from benchmark.readers import program_spans

READ = "mx.serve.decode.read"
DECODE = "jit_decode"
LEAST = 10


def pairs(v):
    """(read span's end, end of the run it waited for) in ns, in order of the
    reads' ends; a read with no run ended before it is left out."""
    ends = sorted(b for _a, b, name in v["modules"] if name == DECODE)
    reads = sorted(b for a, b, name, _t, whole in v["spans"]
                   if name == READ and b - a == whole)
    out = []
    for b in reads:
        i = bisect.bisect_left(ends, b)
        if i:
            out.append((b, ends[i - 1]))
    return out


def values(v, what):
    """Lags or device periods in ns (``what`` as in the module's doc)."""
    p = pairs(v)
    if what == "lag":
        return [b - e for b, e in p]
    # two reads paired with one run (the host more than a step late) are one
    return [e2 - e1 for (_b1, e1), (_b2, e2) in zip(p, p[1:]) if e2 > e1]


def read(ctx, args):
    v = program_spans.for_context(ctx)
    if not v or not v["modules"]:
        return None
    got = values(v, args["what"])
    if len(got) < LEAST:
        return None
    return float(np.percentile(got, args["q"])) / 1e6
