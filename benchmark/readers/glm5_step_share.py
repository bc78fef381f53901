"""Shares of the chip's peaks for the GLM-5 decode step and its two
distinctive parts, from counts of the work the mathematics needs (whatever
implements it) over device time in the traced segment.  ``args.of``:

- ``step_flops``   the whole step's operations over the bf16 peak and the
  device time of the programs matching ``args.match``;
- ``step_bytes``   the least bytes any build must read a step over the
  memory's peak and the same device time;
- ``attn``         the sparse absorbed attention's roofline share (the larger
  of its operations over the bf16 peak and its bytes over the memory's peak)
  over the device self time under the named scope ``args.scope``;
- ``experts``      the routed experts' roofline share, likewise.

The counts come from the configuration's ``program`` group and from what the
program counted over the segment (``profiler.generate_stats``, through the
runner): slot-steps that held a stream, decode steps, token-expert pairs that
fell on held experts, held experts touched, index keys scanned, latent rows
selected.  Percent, never clipped; nothing where the trace, the scope or a
counter is absent (a program that has none of this).
"""
from benchmark.readers import program_spans
from benchmark.trace_reduce import matching

NEEDED = ("active_slot_steps", "decode_steps", "moe_pairs_held", "moe_experts_touched",
          "dsa_keys_scanned", "dsa_keys_selected")


# -- what the mathematics needs, from shapes alone ---------------------------
def expert_params(m):
    """One routed or shared expert: gate, up and down."""
    return 3 * m["d_model"] * m["d_expert"]


def kv_b_params(m):
    """The key-value up-projection, which the absorbed form applies to the
    query (its key half) and to the attended latent (its value half)."""
    return m["kv_rank"] * m["n_heads"] * (m["d_nope"] + m["d_v"])


def layer_matrix_params(m, dense):
    """Matrix parameters every token meets in one layer outside the routed
    experts: the low-rank projections, the output projection, the indexer,
    and the dense FFN or the router with the shared expert."""
    d, h = m["d_model"], m["n_heads"]
    attn = (d * m["q_rank"] + m["q_rank"] * h * (m["d_nope"] + m["d_rope"])
            + d * (m["kv_rank"] + m["d_rope"]) + kv_b_params(m) + h * m["d_v"] * d)
    index = (m["q_rank"] * m["index_heads"] * m["index_dim"] + d * m["index_dim"]
             + d * m["index_heads"])
    ffn = 3 * d * m["d_ff"] if dense else d * m["n_experts"] + expert_params(m)
    return attn + index + ffn


def token_matrix_params(m):
    """Matrix parameters a token meets over all layers and the head, its
    pairs on routed experts apart."""
    dense = m["n_dense_layers"]
    return (dense * layer_matrix_params(m, True)
            + (m["n_layers"] - dense) * layer_matrix_params(m, False)
            + m["vocab"] * m["d_model"])


def index_flops(m, keys_scanned):
    """One multiply-add a head a dim a scanned key."""
    return 2 * m["index_heads"] * m["index_dim"] * keys_scanned


def attn_core_flops(m, keys_selected):
    """Scores against the latent row and the shared rotary key, then P c_kv,
    a head a selected key."""
    return 2 * m["n_heads"] * (2 * m["kv_rank"] + m["d_rope"]) * keys_selected


def step_flops(m, w):
    return (2 * token_matrix_params(m) * w["active_slot_steps"]
            + 2 * expert_params(m) * w["moe_pairs_held"]
            + index_flops(m, w["dsa_keys_scanned"])
            + attn_core_flops(m, w["dsa_keys_selected"]))


def step_bytes(m, w, width=2):
    """Every matrix outside the routed experts and the head once a decode
    step, each touched held expert once, an index key a scanned position, a
    latent row a selected one."""
    return width * (token_matrix_params(m) * w["decode_steps"]
                    + expert_params(m) * w["moe_experts_touched"]
                    + m["index_dim"] * w["dsa_keys_scanned"]
                    + (m["kv_rank"] + m["d_rope"]) * w["dsa_keys_selected"])


def attn_cost(m, w, width=2):
    """The sparse absorbed attention of all layers: the absorbing products
    with ``W_kvb`` a token a layer and the core a selected key; bytes are
    ``W_kvb`` once a layer a step and the selected latent rows."""
    layer_steps = m["n_layers"] * w["decode_steps"]
    flops = (2 * kv_b_params(m) * m["n_layers"] * w["active_slot_steps"]
             + attn_core_flops(m, w["dsa_keys_selected"]))
    bytes_ = width * (kv_b_params(m) * layer_steps
                      + (m["kv_rank"] + m["d_rope"]) * w["dsa_keys_selected"])
    return flops, bytes_


def experts_cost(m, w, width=2):
    """The routed experts held here: a pair's three products, a touched
    expert's three matrices."""
    return (2 * expert_params(m) * w["moe_pairs_held"],
            width * expert_params(m) * w["moe_experts_touched"])


# -- the reader --------------------------------------------------------------
def scope_seconds(ctx, scope):
    """Device self time, a chip, of the operations under a named scope."""
    v = program_spans.for_context(ctx)
    if not v:
        return None
    rows = [r for r in program_spans.hlo_stats(v["path"])
            if scope in (r.get("tf_op_name") or "")]
    if not rows:
        return None
    return sum(r["total_self_time"] for r in rows) / 1e6 / ctx["cell"].chips


def read(ctx, args):
    trace, seg, peaks = ctx["trace"], ctx["segment"], ctx["peaks"]
    if not trace or not seg or not peaks:
        return None
    m = ctx["cell"].config.get("program", {})
    w = seg["work"]
    if any(k not in w for k in NEEDED) or "kv_rank" not in m or w["decode_steps"] <= 0:
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
