"""Seeded weights in the layout of the Hy4-preview program and its plain
reference (``mxnet_tpu/models/mla_moe.py`` ``param_shapes`` with
hyper-connections, a gated attention with sinks, and an indexer on the
``full`` layers alone), made on the device one leaf at a time, in the type the
configuration states, by ``benchmark/weights_longcat.py``'s drawing program
of a fixed size and a cheap one a shape that puts the pieces together.

normal(0, ``init_std``) matrices, unit norm gains and alphas, zero offsets,
the router's correction bias normal(0, ``router_bias_std``), a
hyper-connection's projection normal(0, ``init_std / sqrt(hc_mult)``) (its
input is ``hc_mult`` times wider than ``d``) and its biases normal(0,
``hc_bias_std``), so that the maps differ by stream and by token, and the
sinks normal(``sink_mean``, ``sink_std``).  ``check_layout`` compares names
and shapes with the program's own table, so a program that renames or
reshapes a leaf stops the run before it measures.
"""
import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key
from benchmark.weights_longcat import BLOCK, _assemble, _draw


def full_layers(m):
    """The layers with an indexer of their own."""
    types = m.get("indexer_types") or ["full"] * m["n_layers"]
    return [i for i, kind in enumerate(types) if kind == "full"]


def shapes(m):
    """name -> (shape, kind) for a ``program`` group of the configuration:
    attention, gate, sink and hyper-connection leaves stacked over all
    layers, the indexer's over the full layers, dense FFN leaves over the
    leading dense layers, router and expert leaves over the layers after."""
    d, L, H = m["d_model"], m["n_layers"], m["n_heads"]
    Ld, Lf, n = m["n_dense_layers"], len(full_layers(m)), m["hc_mult"]
    Lm, Eh = L - Ld, len(m["held_experts"])
    qk = m["d_nope"] + m["d_rope"]
    out = {
        "embed_weight": ((m["vocab"], d), "normal"),
        "head_weight": ((m["vocab"], d), "normal"),
        "final_norm": ((d,), "ones"),
        "attn_norm": ((L, d), "ones"),
        "ffn_norm": ((L, d), "ones"),
        "q_a_weight": ((L, d, m["q_rank"]), "normal"),
        "q_a_norm": ((L, m["q_rank"]), "ones"),
        "q_b_weight": ((L, m["q_rank"], H, qk), "normal"),
        "kv_a_weight": ((L, d, m["kv_rank"] + m["d_rope"]), "normal"),
        "kv_a_norm": ((L, m["kv_rank"]), "ones"),
        "kv_b_weight": ((L, m["kv_rank"], H, m["d_nope"] + m["d_v"]), "normal"),
        "o_weight": ((L, H, m["d_v"], d), "normal"),
        "o_gate_weight": ((L, d, H * m["d_v"]), "normal"),
        "attn_sink": ((L, H), "sink"),
        "index_q_weight": ((Lf, m["q_rank"], m["index_heads"], m["index_dim"]), "normal"),
        "index_k_weight": ((Lf, d, m["index_dim"]), "normal"),
        "index_k_norm_gamma": ((Lf, m["index_dim"]), "ones"),
        "index_k_norm_beta": ((Lf, m["index_dim"]), "zeros"),
        "index_w_weight": ((Lf, d, m["index_heads"]), "normal"),
        "dense_gate_weight": ((Ld, d, m["d_ff"]), "normal"),
        "dense_up_weight": ((Ld, d, m["d_ff"]), "normal"),
        "dense_down_weight": ((Ld, m["d_ff"], d), "normal"),
        "router_weight": ((Lm, d, m["n_experts"]), "normal"),
        "router_bias": ((Lm, m["n_experts"]), "bias"),
        "expert_gate_weight": ((Lm, Eh, d, m["d_expert"]), "normal"),
        "expert_up_weight": ((Lm, Eh, d, m["d_expert"]), "normal"),
        "expert_down_weight": ((Lm, Eh, m["d_expert"], d), "normal"),
        "shared_gate_weight": ((Lm, d, m["d_expert"]), "normal"),
        "shared_up_weight": ((Lm, d, m["d_expert"]), "normal"),
        "shared_down_weight": ((Lm, m["d_expert"], d), "normal"),
    }
    for half in ("attn", "ffn"):
        out["hc_%s_proj" % half] = ((L, n * d, n * (n + 2)), "hc")
        out["hc_%s_bias" % half] = ((L, n * (n + 2)), "hc_bias")
        out["hc_%s_scale" % half] = ((L, 3), "ones")
    return out


def leaf(m, seed, name, draws):
    """One leaf on the default device, from the seed and the leaf's place in
    the sorted names: drawn ``BLOCK`` normals at a time (a leaf smaller than
    that in one piece of its own size), scaled by its kind's entry of
    ``draws`` (``normal``, ``bias``, ``hc``, ``hc_bias``, ``sink``), moved by
    ``sink_mean`` where a sink, and put into its shape."""
    table = shapes(m)
    shape, kind = table[name]
    dtype = jnp.dtype(m["dtype"])
    if kind in ("ones", "zeros"):
        return (jnp.ones if kind == "ones" else jnp.zeros)(shape, dtype)
    size = math.prod(shape)
    count = min(size, BLOCK)
    blocks = -(-size // count)
    key, index = seed_key(seed), sorted(table).index(name)
    scale = jnp.float32(draws[kind])
    parts = [_draw(count, dtype)(key, index, b, scale) for b in range(blocks)]
    out = _assemble(tuple(shape), blocks)(*parts)
    if kind == "sink":
        out = jax.jit(lambda x: (x.astype(jnp.float32) + draws["sink_mean"]).astype(dtype))(out)
    return out


def draws(config):
    """The scales of each kind of leaf, from the configuration's top level."""
    c = config
    n = c["program"]["hc_mult"]
    return {"normal": c["init_std"], "bias": c["router_bias_std"],
            "hc": c["init_std"] / math.sqrt(n), "hc_bias": c["hc_bias_std"],
            "sink": c["sink_std"], "sink_mean": c["sink_mean"]}


def params(m, seed, scales):
    """The whole parameter dict, leaf by leaf (a leaf's pieces beside the leaf
    they are put into are the only temporary)."""
    return {name: leaf(m, seed, name, scales) for name in sorted(shapes(m))}


def check_layout(param_shapes, config_cls, m):
    """Names and shapes equal the program's own table at the cell's
    configuration (shapes alone: nothing is allocated)."""
    theirs, ours = param_shapes(config_cls(**m)), shapes(m)
    if set(theirs) != set(ours):
        raise RuntimeError("parameter names differ from the program's: %r"
                           % sorted(set(theirs) ^ set(ours)))
    for k, (shape, _kind) in theirs.items():
        if tuple(shape) != tuple(ours[k][0]):
            raise RuntimeError("parameter %s: the program takes %r, the "
                               "benchmark makes %r" % (k, shape, ours[k][0]))
