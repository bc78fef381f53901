"""Host time of Python's collections (the program's ``mx.host.gc`` spans)
inside the traced segment over the segment's units of ``args.work``, in
milliseconds: 0 where the program marks its collections and none fell in
the segment.  Nothing where the program registers no ``gc.callbacks`` hook
(the parent of the PR that brought it) or the run was not traced."""
import gc

from benchmark.readers import program_spans

SPAN = "mx.host.gc"


def marks_collections():
    return any(getattr(cb, "__module__", None) == "mxnet_tpu.profiler"
               for cb in gc.callbacks)


def read(ctx, args):
    v = program_spans.for_context(ctx)
    if not v or not marks_collections():
        return None
    work = ctx["segment"]["work"].get(args["work"], 0)
    if work <= 0:
        return None
    seconds, _count = program_spans.span_seconds(v, SPAN)
    return 1000.0 * seconds / work
