"""Host time of the program's ``args.span`` spans inside the traced segment
over the segment's units of ``args.work`` (``"spans"``: over the spans
themselves), in milliseconds.  With ``args.self`` true, less what the
``mx.*`` spans nested in them cover; with ``args.less_module`` set, less the
time the compiled programs matching it ran on the device while the span was
open.  Nothing where the program opened no such span."""
import re

from benchmark.readers import program_spans


def read(ctx, args):
    v = program_spans.for_context(ctx)
    if not v:
        return None
    less = re.compile(args["less_module"]) if args.get("less_module") else None
    seconds, count = program_spans.span_seconds(v, args["span"], bool(args.get("self")), less)
    if count == 0:
        return None
    work = count if args["work"] == "spans" else ctx["segment"]["work"].get(args["work"], 0)
    if work <= 0:
        return None
    return 1000.0 * seconds / work
