"""Seeded weights in the layout of the LongCat-Flash program and its plain
reference (``mxnet_tpu/models/scmoe.py`` ``param_shapes``), made on the device
one leaf at a time, in the type the configuration states, by one drawing
program of a fixed size and a cheap one a shape that puts the pieces together.

As ``benchmark/weights_glm5.py``: normal(0, ``init_std``) matrices, unit norm
gains, and the router's correction bias normal(0, ``router_bias_std``) over
all real and identity experts, so that the bias moves the choice.
``check_layout`` compares names and shapes with the program's own table, so a
program that renames or reshapes a leaf stops the run before it measures.
"""
import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key


def shapes(m):
    """name -> (shape, kind) for a ``program`` group of the configuration:
    ``<leaf>.<layer>.<a>`` for attention ``a`` of a double layer and the dense
    FFN after it, ``<leaf>.<layer>`` for its expert layer."""
    d, H, Eh = m["d_model"], m["n_heads"], len(m["held_experts"])
    R = m["n_experts"] + m["n_zero_experts"]
    sublayer = {
        "attn_norm": ((d,), "ones"),
        "ffn_norm": ((d,), "ones"),
        "q_a_weight": ((d, m["q_rank"]), "normal"),
        "q_a_norm": ((m["q_rank"],), "ones"),
        "q_b_weight": ((m["q_rank"], H, m["d_nope"] + m["d_rope"]), "normal"),
        "kv_a_weight": ((d, m["kv_rank"] + m["d_rope"]), "normal"),
        "kv_a_norm": ((m["kv_rank"],), "ones"),
        "k_b_weight": ((m["kv_rank"], H, m["d_nope"]), "normal"),
        "v_b_weight": ((m["kv_rank"], H, m["d_v"]), "normal"),
        "o_weight": ((H, m["d_v"], d), "normal"),
        "dense_gate_weight": ((d, m["d_ff"]), "normal"),
        "dense_up_weight": ((d, m["d_ff"]), "normal"),
        "dense_down_weight": ((m["d_ff"], d), "normal"),
    }
    expert_layer = {
        "router_weight": ((d, R), "normal"),
        "router_bias": ((R,), "bias"),
        "expert_gate_weight": ((Eh, d, m["d_expert"]), "normal"),
        "expert_up_weight": ((Eh, d, m["d_expert"]), "normal"),
        "expert_down_weight": ((Eh, m["d_expert"], d), "normal"),
    }
    out = {"embed_weight": ((m["vocab"], d), "normal"),
           "head_weight": ((m["vocab"], d), "normal"),
           "final_norm": ((d,), "ones")}
    for l in range(m["n_layers"]):
        for a in (0, 1):
            out.update({"%s.%d.%d" % (k, l, a): v for k, v in sublayer.items()})
        out.update({"%s.%d" % (k, l): v for k, v in expert_layer.items()})
    return out


BLOCK = 1 << 22          # normals a call of the one drawing program


@functools.lru_cache(maxsize=None)
def _draw(count, dtype):
    """``count`` scaled normals in ``dtype``; the leaf's index, the block's and
    the scale are arguments, so every leaf of every shape shares the program
    (the chip's compiler takes 4-14 s for each drawing program of a whole
    matrix's shape and under a second for this one: my chip run, PR 38)."""
    def make(key, index, block, scale):
        key = jax.random.fold_in(jax.random.fold_in(key, index), block)
        return (scale * jax.random.normal(key, (count,), jnp.float32)).astype(dtype)

    return jax.jit(make)


@functools.lru_cache(maxsize=None)
def _assemble(shape, blocks):
    size = math.prod(shape)
    return jax.jit(lambda *parts: jnp.concatenate(parts)[:size].reshape(shape))


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
