"""Seeded weights in the layout of the GLM-5 program and its plain reference
(``mxnet_tpu/models/mla_moe.py`` ``param_shapes``), made on the device one
leaf at a time, in the type the configuration states.

The benchmark owns these as it owns ``benchmark/weights.py``'s: program and
reference are both handed them.  normal(0, ``init_std``) matrices, unit norm
gains, zero offsets, and the router's correction bias normal(0,
``router_bias_std``), so that the bias moves the choice (a trained model's
is not zero either).  ``check_layout`` compares names and shapes with the
program's own table at a tiny size, so a program that renames or reshapes a
leaf stops the run before it measures.
"""
import jax
import jax.numpy as jnp

from benchmark.weights import seed_key


def shapes(m):
    """name -> (shape, kind) for a ``program`` group of the configuration."""
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
        "q_a_weight": ((L, d, m["q_rank"]), "normal"),
        "q_a_norm": ((L, m["q_rank"]), "ones"),
        "q_b_weight": ((L, m["q_rank"], H, qk), "normal"),
        "kv_a_weight": ((L, d, m["kv_rank"] + m["d_rope"]), "normal"),
        "kv_a_norm": ((L, m["kv_rank"]), "ones"),
        "kv_b_weight": ((L, m["kv_rank"], H, m["d_nope"] + m["d_v"]), "normal"),
        "o_weight": ((L, H, m["d_v"], d), "normal"),
        "index_q_weight": ((L, m["q_rank"], m["index_heads"], m["index_dim"]), "normal"),
        "index_k_weight": ((L, d, m["index_dim"]), "normal"),
        "index_k_norm_gamma": ((L, m["index_dim"]), "ones"),
        "index_k_norm_beta": ((L, m["index_dim"]), "zeros"),
        "index_w_weight": ((L, d, m["index_heads"]), "normal"),
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
    the sorted names."""
    table = shapes(m)
    shape, kind = table[name]
    dtype = jnp.dtype(m["dtype"])
    index = sorted(table).index(name)

    def make(key):
        if kind == "ones":
            return jnp.ones(shape, dtype)
        if kind == "zeros":
            return jnp.zeros(shape, dtype)
        scale = bias_std if kind == "bias" else init_std
        draw = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
        return (scale * draw).astype(dtype)

    return jax.jit(make)(seed_key(seed))


def params(m, seed, init_std, bias_std):
    """The whole parameter dict, leaf by leaf (the largest leaf's float32
    draw is the only temporary)."""
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
