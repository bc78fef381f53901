"""Weights made on the device, in one jitted call, from ``--seed``.

The benchmark owns its weights: the program and the plain reference are both
handed these, so neither takes anything the other made.  Keys, shapes and
scales are those the program's entry points expect (``init_params`` of
``mxnet_tpu/models/transformer.py``: normal(0, 0.02) matrices, unit LayerNorm
gains, zero LayerNorm offsets, float32); ``check_layout`` compares key set and
ranks against ``init_params`` of a two-layer configuration at start-up, so a
program that renames or reshapes a parameter stops the run before it measures.
"""
import jax
import jax.numpy as jnp

INIT_SCALE = 0.02


def seed_key(seed):
    """A PRNG key from any non-negative whole seed, also past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def lm_shapes(c):
    """name -> (shape, kind) for a model config dict (vocab, d_model, n_heads,
    n_layers, d_ff, max_len)."""
    d, h, l, f = c["d_model"], c["n_heads"], c["n_layers"], c["d_ff"]
    dh = d // h
    return {
        "embed_weight": ((c["vocab"], d), "normal"),
        "pos_embed_weight": ((c["max_len"], d), "normal"),
        "final_ln_gamma": ((d,), "ones"),
        "final_ln_beta": ((d,), "zeros"),
        "ln1_gamma": ((l, d), "ones"),
        "ln1_beta": ((l, d), "zeros"),
        "ln2_gamma": ((l, d), "ones"),
        "ln2_beta": ((l, d), "zeros"),
        "attn_qkv_weight": ((l, d, 3, h, dh), "normal"),
        "attn_out_weight": ((l, h, dh, d), "normal"),
        "ffn_up_weight": ((l, d, f), "normal"),
        "ffn_down_weight": ((l, f, d), "normal"),
    }


def _leaf(key, index, shape, kind, scale):
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    return scale * jax.random.normal(jax.random.fold_in(key, index), shape,
                                     jnp.float32)


def lm_leaf(c, seed, name, scale=INIT_SCALE):
    """One leaf alone, the same values ``lm_params`` gives it."""
    shapes = lm_shapes(c)
    index = sorted(shapes).index(name)
    shape, kind = shapes[name]
    return jax.jit(lambda k: _leaf(k, index, shape, kind, scale))(seed_key(seed))


def lm_params(c, seed, scale=INIT_SCALE):
    """The whole float32 parameter dict, generated on the default device in
    one jitted call.  ``scale`` is the configuration's ``init_std``."""
    shapes = lm_shapes(c)

    def make(key):
        return {name: _leaf(key, i, *shapes[name], scale)
                for i, name in enumerate(sorted(shapes))}

    return jax.jit(make)(seed_key(seed))


def check_layout(init_params, config_cls):
    """Key set and ranks equal the program's own ``init_params`` (two layers,
    tiny widths: a host draw of a few thousand values)."""
    tiny = dict(vocab=64, d_model=16, n_heads=2, n_layers=2, d_ff=32, max_len=8)
    theirs = init_params(config_cls(**tiny))
    ours = lm_shapes(tiny)
    if set(theirs) != set(ours):
        raise RuntimeError("parameter names differ from init_params: %r"
                           % sorted(set(theirs) ^ set(ours)))
    for k, v in theirs.items():
        if tuple(v.shape) != ours[k][0]:
            raise RuntimeError("parameter %s: init_params gives %r, the "
                               "benchmark makes %r" % (k, v.shape, ours[k][0]))


def resnet_params(shapes, seed):
    """ResNet weights on the host from the seed, as float32 numpy arrays:
    Xavier-uniform convolutions and classifier (MXNet's default
    ``Xavier()``: uniform within sqrt(3 / ((fan_in + fan_out) / 2))), unit
    BatchNorm gains and moving variances, zero offsets, biases and moving
    means.  ``shapes`` is ``(args, aux)`` of the plain reference's
    ``param_shapes``: 25.5 M values for ResNet-50, a fraction of a second."""
    import numpy as np

    rng = np.random.default_rng([int(seed), 3])
    args, aux = {}, {}
    for name in sorted(shapes[0]):
        shape = shapes[0][name]
        if name.endswith("_weight"):
            receptive = int(np.prod(shape[2:])) if len(shape) > 2 else 1
            fan_in, fan_out = shape[1] * receptive, shape[0] * receptive
            bound = float(np.sqrt(3.0 / ((fan_in + fan_out) / 2.0)))
            args[name] = rng.uniform(-bound, bound, shape).astype(np.float32)
        elif name.endswith("_gamma"):
            args[name] = np.ones(shape, np.float32)
        else:
            args[name] = np.zeros(shape, np.float32)
    for name, shape in shapes[1].items():
        aux[name] = (np.ones if name.endswith("_var") else np.zeros)(shape, np.float32)
    return args, aux
