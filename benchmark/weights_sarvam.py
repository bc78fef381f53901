"""Seeded weights in the layout of the Sarvam-105B program and its plain
reference (``mxnet_tpu/models/mla_moe.py`` ``param_shapes`` with a full-rank
query, q and k norms and no indexer), made on the device one leaf at a time,
in the type the configuration states, by ``benchmark/weights_longcat.py``'s
drawing program of a fixed size (one compile for every leaf) and a cheap one
a shape that puts the pieces together.

normal(0, ``init_std``) matrices, unit norm gains, and the router's correction
bias normal(0, ``router_bias_std``), so that the bias moves the choice.
``check_layout`` compares names and shapes with the program's own table, so a
program that renames or reshapes a leaf stops the run before it measures.
"""
import math

import jax.numpy as jnp

from benchmark.weights import seed_key
from benchmark.weights_longcat import BLOCK, _assemble, _draw


def shapes(m):
    """name -> (shape, kind) for a ``program`` group of the configuration:
    attention leaves stacked over all layers, dense FFN leaves over the
    leading dense layers, router and expert leaves over the layers after."""
    d, L, H = m["d_model"], m["n_layers"], m["n_heads"]
    Ld = m["n_dense_layers"]
    Lm, Eh = L - Ld, len(m["held_experts"])
    qk = m["d_nope"] + m["d_rope"]
    return {
        "embed_weight": ((m["vocab"], d), "normal"),
        "head_weight": ((m["vocab"], d), "normal"),
        "final_norm": ((d,), "ones"),
        "attn_norm": ((L, d), "ones"),
        "ffn_norm": ((L, d), "ones"),
        "q_weight": ((L, d, H, qk), "normal"),
        "q_norm": ((L, qk), "ones"),
        "kv_a_weight": ((L, d, m["kv_rank"] + m["d_rope"]), "normal"),
        "kv_a_norm": ((L, m["kv_rank"]), "ones"),
        "k_norm": ((L, m["d_rope"]), "ones"),
        "kv_b_weight": ((L, m["kv_rank"], H, m["d_nope"] + m["d_v"]), "normal"),
        "o_weight": ((L, H, m["d_v"], d), "normal"),
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


def leaf(m, seed, name, init_std, bias_std):
    """One leaf on the default device, from the seed and the leaf's place in
    the sorted names: drawn ``BLOCK`` normals at a time (a leaf smaller than
    that in one piece of its own size) and put into its shape."""
    table = shapes(m)
    shape, kind = table[name]
    dtype = jnp.dtype(m["dtype"])
    if kind == "ones":
        return jnp.ones(shape, dtype)
    size = math.prod(shape)
    count = min(size, BLOCK)
    blocks = -(-size // count)
    key, index = seed_key(seed), sorted(table).index(name)
    scale = jnp.float32(bias_std if kind == "bias" else init_std)
    parts = [_draw(count, dtype)(key, index, b, scale) for b in range(blocks)]
    return _assemble(tuple(shape), blocks)(*parts)


def params(m, seed, init_std, bias_std):
    """The whole parameter dict, leaf by leaf (a leaf's pieces beside the leaf
    they are put into are the only temporary)."""
    return {name: leaf(m, seed, name, init_std, bias_std) for name in sorted(shapes(m))}


def check_layout(param_shapes, config_cls, m):
    """Names and shapes equal the program's own table at the cell's
    configuration (shapes alone: nothing is allocated)."""
    theirs = param_shapes(config_cls(**m))
    ours = shapes(m)
    if set(theirs) != set(ours):
        raise RuntimeError("parameter names differ from the program's: %r"
                           % sorted(set(theirs) ^ set(ours)))
    for k, (shape, _kind) in theirs.items():
        if tuple(shape) != tuple(ours[k][0]):
            raise RuntimeError("parameter %s: the program takes %r, the "
                               "benchmark makes %r" % (k, shape, ours[k][0]))
