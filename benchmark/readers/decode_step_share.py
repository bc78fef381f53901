"""The decode step's share of one of the chip's peaks, over the device time
of the compiled programs whose names match ``args.match`` in the traced
segment.  ``args.of`` says which: ``flops`` is 2 x the parameters that take
part in matrix products (``flops.lm_matrix_params``) a generated token, times
the segment's ``active_slot_steps``, over the bf16 peak; ``bytes`` is those
parameters at ``args.bytes_per_param`` read once a decode step, times the
segment's ``decode_steps``, over the memory's peak.  Percent, never clipped;
nothing where no such program ran."""
from benchmark import flops
from benchmark.trace_reduce import matching


def read(ctx, args):
    trace, seg, peaks = ctx["trace"], ctx["segment"], ctx["peaks"]
    if not trace or not seg or not peaks:
        return None
    seconds, runs = matching(trace["modules"], args["match"])
    if runs == 0 or seconds <= 0:
        return None
    cell = ctx["cell"]
    params = flops.lm_matrix_params(cell.config["program"])
    if args["of"] == "flops":
        least = 2 * params * seg["work"].get("active_slot_steps", 0) / peaks["bf16_flops_per_s"]
    else:
        least = (args["bytes_per_param"] * params * seg["work"].get("decode_steps", 0)
                 / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds * cell.chips)
