"""Shares of the chip's memory and compute peaks for the Hy4-preview decode
step (GLM-5's latent attention and experts with four hyper-connected streams,
a gated attention with sinks, an indexer on the ``full`` layers alone whose
selection the ``shared`` layers reuse, and a float32 head), from counts of the
work the mathematics needs (whatever implements it) over the device time of
the programs matching ``args.match`` in the traced segment.  ``args.of``:

- ``step_flops``   the whole step's operations over the bf16 peak;
- ``step_bytes``   the least bytes any build must read a step over the
  memory's peak.

GLM-5's counts (``glm5_step_share``) with what this model adds: a layer's
output gate and two hyper-connection projections among the matrices every
token meets, the indexer on the full layers only (its keys are what
``dsa_keys_scanned`` counts), the streams' reads, mixes and writes (``2 n^2 d
+ 4 n d`` operations a token a sublayer), and the float32 head as three
bfloat16 products (the least a bf16 unit needs for a float32 product with a
bfloat16 operand: the rows as three bfloat16 parts); its bytes stay the
bfloat16 rows, read once.  Percent, never clipped; nothing where the trace or
a counter is absent, or the configuration has no ``hc_mult`` or its program
counts no ``dsa_selections_reused`` (GLM-5's program, and a parent that
cannot run this cell).
"""
from benchmark.readers import glm5_step_share as glm
from benchmark.trace_reduce import matching

NEEDED = glm.NEEDED + ("dsa_selections_reused",)
HEAD_PARTS = 3       # bfloat16 products a float32 head's rows take


# -- what the mathematics needs, from shapes alone ---------------------------
def full_layers(m):
    types = m.get("indexer_types") or ["full"] * m["n_layers"]
    return [i for i, kind in enumerate(types) if kind == "full"]


def index_params(m):
    """One full layer's indexer: W_Iq, W_Ik and W_Iw."""
    d = m["d_model"]
    return (m["q_rank"] * m["index_heads"] * m["index_dim"] + d * m["index_dim"]
            + d * m["index_heads"])


def added_layer_params(m):
    """What a layer adds to GLM-5's: the output gate and the two sublayers'
    hyper-connection projections."""
    d, n = m["d_model"], m["hc_mult"]
    return d * m["n_heads"] * m["d_v"] + 2 * (n * d) * n * (n + 2)


def token_matrix_params(m):
    """Matrix parameters a token meets over all layers and the head, its
    pairs on routed experts apart; the indexer on the full layers only."""
    shared = m["n_layers"] - len(full_layers(m))
    return (glm.token_matrix_params(m) + m["n_layers"] * added_layer_params(m)
            - shared * index_params(m))


def stream_flops(m):
    """A token's hyper-connections besides their projections: over 2 L
    sublayers, ``u = sum_i H_pre[i] X[i]`` and ``X[i] <- sum_j H_res[i, j]
    X[j] + H_post[i] y``."""
    d, n = m["d_model"], m["hc_mult"]
    return 2 * m["n_layers"] * (2 * n * n * d + 4 * n * d)


def step_flops(m, w):
    head = m["vocab"] * m["d_model"]
    return (2 * (token_matrix_params(m) + (HEAD_PARTS - 1) * head) * w["active_slot_steps"]
            + stream_flops(m) * w["active_slot_steps"]
            + 2 * glm.expert_params(m) * w["moe_pairs_held"]
            + glm.index_flops(m, w["dsa_keys_scanned"])
            + glm.attn_core_flops(m, w["dsa_keys_selected"]))


def step_bytes(m, w, width=2):
    """Every matrix outside the routed experts once a decode step, each
    touched held expert once, an index key a scanned position (full layers),
    a latent row a selected one (every layer)."""
    return width * (token_matrix_params(m) * w["decode_steps"]
                    + glm.expert_params(m) * w["moe_experts_touched"]
                    + m["index_dim"] * w["dsa_keys_scanned"]
                    + (m["kv_rank"] + m["d_rope"]) * w["dsa_keys_selected"])


# -- the reader --------------------------------------------------------------
def read(ctx, args):
    trace, seg, peaks = ctx["trace"], ctx["segment"], ctx["peaks"]
    if not trace or not seg or not peaks:
        return None
    m = ctx["cell"].config.get("program", {})
    w = seg["work"]
    if any(k not in w for k in NEEDED) or "hc_mult" not in m or w["decode_steps"] <= 0:
        return None
    seconds, runs = matching(trace["modules"], args["match"])
    if runs == 0 or seconds <= 0:
        return None
    least = step_flops(m, w) / peaks["bf16_flops_per_s"] if args["of"] == "step_flops" \
        else step_bytes(m, w) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / (seconds * ctx["cell"].chips)
