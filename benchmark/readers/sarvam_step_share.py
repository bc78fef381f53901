"""Shares of the chip's memory and compute peaks for the Sarvam-105B decode
step (latent attention with a full-rank query over every cached row, no
indexer) from counts of the work the mathematics needs (whatever implements
it) over device time in the traced segment.  ``args.of``:

- ``step_flops``   the whole step's operations over the bf16 peak and the
  device time of the programs matching ``args.match``;
- ``step_bytes``   the least bytes any build must read a step over the
  memory's peak and the same device time;
- ``attn``         the dense absorbed attention's roofline share (the larger
  of its operations over the bf16 peak and its bytes over the memory's peak)
  over the device self time under the named scope ``args.scope``;
- ``experts``      the held routed experts' roofline share, likewise.

The counts come from the configuration's ``program`` group and from what the
program counted over the segment (``profiler.generate_stats``, through the
runner): slot-steps that held a stream, decode steps, token-expert pairs that
fell on held experts, held experts touched, cached latent rows attended
(summed over the layers).  Percent, never
clipped; nothing where the trace, the scope or a counter is absent, or the
configuration is not of this kind (a low-rank query or an indexer in its
``program``).
"""
from benchmark.readers.glm5_step_share import scope_seconds
from benchmark.readers.longcat_step_share import (attn_core_flops, expert_params,
                                                  experts_cost, kv_b_params, row_bytes)
from benchmark.trace_reduce import matching

NEEDED = ("active_slot_steps", "decode_steps", "moe_pairs_held", "moe_experts_touched",
          "attn_rows_read")


# -- what the mathematics needs, from shapes alone ---------------------------
def attn_matrix_params(m):
    """One layer's attention: the full-rank query, the latent projection,
    the up-projection and the output projection."""
    d, h = m["d_model"], m["n_heads"]
    return (d * h * (m["d_nope"] + m["d_rope"]) + d * (m["kv_rank"] + m["d_rope"])
            + kv_b_params(m) + h * m["d_v"] * d)


def token_matrix_params(m):
    """Matrix parameters a token meets over all layers and the head, its
    pairs on routed experts apart: the dense layers' FFN, the expert layers'
    router and shared expert."""
    d, dense = m["d_model"], m["n_dense_layers"]
    ffn = dense * 3 * d * m["d_ff"] \
        + (m["n_layers"] - dense) * (d * m["n_experts"] + expert_params(m))
    return m["n_layers"] * attn_matrix_params(m) + ffn + m["vocab"] * d


def step_flops(m, w):
    """Every matrix a token meets, its pairs on held experts, and the
    absorbed attention's core a cached row."""
    return (2 * token_matrix_params(m) * w["active_slot_steps"]
            + 2 * expert_params(m) * w["moe_pairs_held"]
            + attn_core_flops(m, w["attn_rows_read"]))


def step_bytes(m, w, width=2):
    """Every matrix outside the routed experts once a decode step, each
    touched held expert once, every attended row once a layer."""
    return (width * (token_matrix_params(m) * w["decode_steps"]
                     + expert_params(m) * w["moe_experts_touched"])
            + row_bytes(m, width) * w["attn_rows_read"])


def attn_cost(m, w, width=2):
    """The dense absorbed attention of all layers: the absorbing products
    with ``W_kvb`` a token a layer and the core a cached row; bytes are
    ``W_kvb`` once a layer a step and the attended rows."""
    layers = m["n_layers"]
    flops = (2 * kv_b_params(m) * layers * w["active_slot_steps"]
             + attn_core_flops(m, w["attn_rows_read"]))
    bytes_ = (width * kv_b_params(m) * layers * w["decode_steps"]
              + row_bytes(m, width) * w["attn_rows_read"])
    return flops, bytes_


# -- the reader --------------------------------------------------------------
def read(ctx, args):
    trace, seg, peaks = ctx["trace"], ctx["segment"], ctx["peaks"]
    if not trace or not seg or not peaks:
        return None
    m = ctx["cell"].config.get("program", {})
    w = seg["work"]
    if any(k not in w for k in NEEDED) or m.get("q_rank", 1) != 0 \
            or m.get("indexer", True) or w["decode_steps"] <= 0:
        return None
    flop_s, byte_s = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    if args["of"] in ("step_flops", "step_bytes"):
        seconds, runs = matching(trace["modules"], args["match"])
        if runs == 0 or seconds <= 0:
            return None
        least = step_flops(m, w) / flop_s if args["of"] == "step_flops" \
            else step_bytes(m, w) / byte_s
        return 100.0 * least / (seconds * ctx["cell"].chips)
    seconds = scope_seconds(ctx, args["scope"])
    if not seconds:
        return None
    flops, bytes_ = attn_cost(m, w) if args["of"] == "attn" else experts_cost(m, w)
    return 100.0 * max(flops / flop_s, bytes_ / byte_s) / seconds
