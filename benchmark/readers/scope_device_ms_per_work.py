"""Device self time of the operations the program named, over the traced
segment's units of ``args.work``, in milliseconds, a chip: those whose
``op_name`` metadata holds ``args.scope`` (a ``jax.named_scope``), those
whose own HLO name matches ``args.op`` (a kernel's ``name=``), or both.
Nothing where no operation carries the name."""
import re

from benchmark.readers import program_spans


def read(ctx, args):
    v = program_spans.for_context(ctx)
    if not v:
        return None
    work = ctx["segment"]["work"].get(args["work"], 0)
    op = re.compile(args.get("op", ""))
    rows = [r for r in program_spans.hlo_stats(v["path"])
            if args.get("scope", "") in (r.get("tf_op_name") or "")
            and op.search(r.get("hlo_op_name") or "")]
    if not rows or work <= 0:
        return None
    us = sum(r["total_self_time"] for r in rows)
    return us / 1000.0 / work / ctx["cell"].chips
