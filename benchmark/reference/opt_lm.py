"""Plain reference for the OPT-style decoder the benchmark's LM cells run.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
learned positions, pre-LayerNorm blocks (eps 1e-5), multi-head causal
attention with materialized scores, ReLU FFN, final LayerNorm, output head
tied to the embedding, mean next-token cross entropy, Adam with bias
correction (``lr_t = lr * sqrt(1 - b2**t) / (1 - b1**t)``, epsilon outside
the root, no weight decay).  No kernels, no cache, no batching.  It imports
nothing of ``mxnet_tpu`` and takes nothing the program made: its weights come
from the benchmark's generator (``benchmark/weights.py``) and its tokens from
the traffic generator.

Departures from facebook/opt-1.3b, the same as the program's block and listed
in the configuration files: no linear biases, and position rows indexed from 0
(OPT stores 2050 rows and offsets by 2).

The parameter dict is the layout the program's entry points take (layers
stacked on a leading axis): ``attn_qkv_weight`` (L, d, 3, H, dh),
``attn_out_weight`` (L, H, dh, d), ``ffn_up_weight`` (L, d, f),
``ffn_down_weight`` (L, f, d), ``ln{1,2}_{gamma,beta}`` (L, d),
``embed_weight`` (V, d), ``pos_embed_weight`` (P, d), ``final_ln_*`` (d,).

``quant`` puts a lower precision in the reference's place for the control:
a pair ``(operand, cotangent)`` from ``benchmark/reference/precision.py``.  Both operands of every
projection, of the FFN and of the head, and q, k and v, pass through the
first on the way forward; the gradient that flows back into each of those
products passes through the second, so that the backward products have
low-precision operands too, as a step computed in that precision has.
Layers are recomputed in the backward pass (``jax.checkpoint``) and attention
runs one batch row at a time, so that the whole thing fits beside Adam's state.
"""
import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.precision import EXACT

LN_EPS = 1e-5
SHARED = ("embed_weight", "pos_embed_weight", "final_ln_gamma", "final_ln_beta")


def layernorm(x, gamma, beta):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + LN_EPS) * gamma + beta


def _attention_row(q, k, v):
    """(H, S, dh) each, one batch row: causal softmax(q k^T / sqrt(dh)) v."""
    s = q.shape[1]
    scores = jnp.einsum("hqe,hke->hqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    return jnp.einsum("hqk,hke->hqe", jax.nn.softmax(scores, axis=-1), v)


def block(x, lp, quant=EXACT):
    """One decoder block. x: (B, S, d) float32."""
    q_in, q_back = quant
    h = q_in(layernorm(x, lp["ln1_gamma"], lp["ln1_beta"]))
    qkv = q_back(jnp.einsum("bsd,dthe->tbhse", h, q_in(lp["attn_qkv_weight"])))
    q, k, v = q_in(qkv[0]), q_in(qkv[1]), q_in(qkv[2])
    o = lax.map(jax.checkpoint(lambda t: _attention_row(*t)), (q, k, v))
    x = x + q_back(jnp.einsum("bhse,hed->bsd", q_in(o), q_in(lp["attn_out_weight"])))
    h = q_in(layernorm(x, lp["ln2_gamma"], lp["ln2_beta"]))
    up = jax.nn.relu(q_back(jnp.einsum("bsd,df->bsf", h, q_in(lp["ffn_up_weight"]))))
    return x + q_back(jnp.einsum("bsf,fd->bsd", q_in(up), q_in(lp["ffn_down_weight"])))


def hidden(params, tokens, quant=EXACT):
    """Final-LayerNorm output (B, S, d) for tokens (B, S) int32."""
    s = tokens.shape[1]
    x = params["embed_weight"][tokens] + params["pos_embed_weight"][:s]
    stacked = {k: v for k, v in params.items() if k not in SHARED}
    layer = jax.checkpoint(lambda x, lp: (block(x, lp, quant), None))
    x, _ = lax.scan(layer, x, stacked)
    return layernorm(x, params["final_ln_gamma"], params["final_ln_beta"])


def head(params, x, quant=EXACT):
    """Next-token logits of final-LayerNorm rows x (..., d): the tied head."""
    q_in, q_back = quant
    return q_back(jnp.einsum("...d,vd->...v", q_in(x), q_in(params["embed_weight"])))


def logits(params, tokens, quant=EXACT):
    """(B, S, V) float32 next-token logits."""
    return head(params, hidden(params, tokens, quant), quant)


def loss(params, tokens, quant=EXACT):
    """Mean cross entropy of tokens[:, 1:] given tokens[:, :-1]."""
    lg = logits(params, tokens[:, :-1], quant)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def adam_init(params):
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    return zeros, {k: jnp.zeros_like(v) for k, v in params.items()}


def train_step(params, m, v, t, tokens, opt, quant=EXACT, rows=None):
    """One Adam step at step number ``t`` (1-based). Returns the new
    (params, m, v), the loss and the per-leaf gradient norms.  ``rows``
    plants the half-batch fault: the loss is the mean over those rows only."""
    if rows is not None:
        tokens = tokens[jnp.asarray(rows)]
    value, grads = jax.value_and_grad(loss)(params, tokens, quant)
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["epsilon"], opt["learning_rate"]
    tf = jnp.float32(t)
    lr_t = lr * jnp.sqrt(1.0 - b2 ** tf) / (1.0 - b1 ** tf)
    new_p, new_m, new_v, gnorm = {}, {}, {}, {}
    for k, w in params.items():
        g = grads[k]
        gnorm[k] = jnp.sqrt(jnp.sum(jnp.square(g)))
        new_m[k] = b1 * m[k] + (1.0 - b1) * g
        new_v[k] = b2 * v[k] + (1.0 - b2) * g * g
        new_p[k] = w - lr_t * new_m[k] / (jnp.sqrt(new_v[k]) + eps)
    return new_p, new_m, new_v, value, gnorm
