"""Plain reference for the LongCat-Flash decoder (shortcut-connected double
layers, softmax routing with identity "zero-compute" experts, dense latent
attention) as one chip of an expert-parallel pool holds it.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``,
written from the equations of the configuration's sources (the catalog row's
``config`` and ``described_as``, the LongCat-Flash report, the public
``transformers`` ``modeling_longcat_flash.py``), not from the program: no cache,
no kernels, no batching, nothing of ``mxnet_tpu``.  One sequence at a time,
every position attending every earlier one.

A double layer ``l`` on input ``x``, with ``N`` an RMSNorm (eps ``norm_eps``)
that has a gain of its own wherever it stands:

    x1 = x  + MLA[l,0](N(x))        h1 = N(x1)       m = MoE[l](h1)
    x2 = x1 + FFN[l,0](h1)
    x3 = x2 + MLA[l,1](N(x2))       h3 = N(x3)
    x4 = x3 + FFN[l,1](h3) + m

- ``FFN`` is SwiGLU: ``(silu(h W_gate) * (h W_up)) W_down``, width ``d_ff``.
- ``MLA``: ``c_q = N(h W_qa)``; ``[q_nope | q_rope] = c_q W_qb`` a head, both
  times ``(d_model / q_rank)^1/2`` (``mla_scale_q_lora``); ``[c_kv | k_rope] =
  h W_kva``; ``c_kv = N(c_kv) * (d_model / kv_rank)^1/2``
  (``mla_scale_kv_lora``), ``k_rope`` unscaled; interleaved rotary
  (``rope_theta``, no scaling) on ``q_rope`` and on ``k_rope``, which the heads
  share; ``k_nope = c_kv W_kb``, ``v = c_kv W_vb`` a head; scores ``(q_nope .
  k_nope + q_rope . k_rope) / sqrt(d_nope + d_rope)``, causal softmax over all
  earlier positions, ``concat_h(P v) W_o``.
- ``MoE``: ``p = softmax(h1 W_r)`` over ``n_experts + n_zero_experts`` router
  outputs; the ``experts_per_token`` largest of ``p + b`` are chosen; ``g_i =
  route_scale * p_i`` with no renormalisation; the output is ``sum g_i
  SwiGLU_i(h1)`` over the chosen real experts **that this chip holds**
  (``held_experts``; the others lie on other chips and their part is left out,
  here as in the program) plus ``(sum of g_i over the chosen identity experts,
  ids >= n_experts) * h1``, whole (the token's home chip computes it).  No
  shared expert.
- head: ``N``, then the untied head over the rows of the vocabulary held.

The parameter dict has the program's layout (``mxnet_tpu/models/scmoe.py``
``param_shapes``): every leaf one sublayer's own, ``<leaf>.<layer>.<a>`` for
attention ``a`` of a double layer with the dense FFN after it, ``router_*.<layer>``
and ``expert_*.<layer>`` (held experts only, in the order of ``held_experts``)
for its expert layer; the key-value up-projection is the two leaves
``k_b_weight`` and ``v_b_weight``.  Leaves may be bfloat16: a sublayer's are
widened where they are used, one sublayer and one expert at a time;
attention goes a group of heads and a block of rows at a time, the other
products a block of rows at a time, so that a 14 k-token sequence at the
published widths fits one chip.

``quant`` puts a lower precision in the reference's place for the control: a
pair from ``benchmark/reference/precision.py`` whose first member rounds both
operands of every product (projections, attention scores and values, router,
experts, FFNs, head).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.precision import EXACT

SUBLAYER_LEAVES = ("attn_norm", "ffn_norm", "q_a_weight", "q_a_norm", "q_b_weight",
                   "kv_a_weight", "kv_a_norm", "k_b_weight", "v_b_weight", "o_weight",
                   "dense_gate_weight", "dense_up_weight", "dense_down_weight")
MOE_LEAVES = ("router_weight", "router_bias", "expert_gate_weight",
              "expert_up_weight", "expert_down_weight")
ROWS = 2048          # rows a block of the per-token products
ATTN_ROWS = 256      # query rows a block of attention
HEAD_GROUP = 8       # heads a group of attention


def f32(x):
    return x.astype(jnp.float32)


def rmsnorm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * f32(gamma)


def rotary(x, positions, theta):
    """Interleaved pairs: (x[2i], x[2i+1]) turned by positions * theta^(-2i/n).
    x is (T, n) or (T, heads, n)."""
    n = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n))
    ang = f32(positions)[:, None] * inv
    if x.ndim == 3:
        ang = ang[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def by_rows(fn, block, *arrays):
    """``fn`` over blocks of rows of the arrays (the last block padded with
    zeros and cut off again)."""
    t = arrays[0].shape[0]
    block = min(block, t)
    pad = -t % block
    split = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
             .reshape((-1, block) + a.shape[1:]) for a in arrays]
    out = lax.map(lambda xs: fn(*xs), tuple(split))
    return jax.tree.map(lambda o: o.reshape((-1,) + o.shape[2:])[:t], out)


def swiglu(h, gate, up, down, q):
    g = jnp.einsum("td,df->tf", q(h), q(f32(gate)))
    u = jnp.einsum("td,df->tf", q(h), q(f32(up)))
    return jnp.einsum("tf,fd->td", q(jax.nn.silu(g) * u), q(f32(down)))


def q_scale(m):
    return (m["d_model"] / m["q_rank"]) ** 0.5 if m.get("scale_q_lora", True) else 1.0


def kv_scale(m):
    return (m["d_model"] / m["kv_rank"]) ** 0.5 if m.get("scale_kv_lora", True) else 1.0


def projections(h, positions, sp, m, q):
    """Per token: c_q, the scaled c_kv and the rotated k_rope."""
    c_q = rmsnorm(jnp.einsum("td,dr->tr", q(h), q(f32(sp["q_a_weight"]))),
                  sp["q_a_norm"], m["norm_eps"])
    kv = jnp.einsum("td,dr->tr", q(h), q(f32(sp["kv_a_weight"])))
    c_kv = rmsnorm(kv[:, :m["kv_rank"]], sp["kv_a_norm"], m["norm_eps"]) * kv_scale(m)
    k_rope = rotary(kv[:, m["kv_rank"]:], positions, m["rope_theta"])
    return c_q, c_kv, k_rope


def attention(c_q, c_kv, k_rope, positions, sp, m, q):
    """Causal softmax over every earlier position, a group of heads and a
    block of query rows at a time; returns concat_h(P v) W_o, (T, d)."""
    nope, dv, heads = m["d_nope"], m["d_v"], m["n_heads"]
    group = min(HEAD_GROUP, heads)
    scale = (nope + m["d_rope"]) ** -0.5
    t = c_q.shape[0]

    def grouped(w, lead):
        """(..., heads, e) -> (heads / group, ..., group, e)"""
        w = f32(w).reshape(w.shape[:lead] + (heads // group, group) + w.shape[lead + 1:])
        return jnp.moveaxis(w, lead, 0)

    q_b, k_b, v_b = grouped(sp["q_b_weight"], 1), grouped(sp["k_b_weight"], 1), \
        grouped(sp["v_b_weight"], 1)
    o_w = grouped(sp["o_weight"], 0)                        # (n, g, dv, d)
    k_rope = q(k_rope)

    def head_group(ws):
        q_b, k_b, v_b, o_w = ws
        k_nope = q(jnp.einsum("sr,rge->sge", q(c_kv), q(k_b)))
        v = q(jnp.einsum("sr,rge->sge", q(c_kv), q(v_b)))

        def block(cq_rows, pos):
            qh = jnp.einsum("tr,rge->tge", q(cq_rows), q(q_b)) * q_scale(m)
            q_nope = q(qh[..., :nope])
            q_rope = q(rotary(qh[..., nope:], pos, m["rope_theta"]))
            s = (jnp.einsum("tge,sge->gts", q_nope, k_nope)
                 + jnp.einsum("tge,se->gts", q_rope, k_rope)) * scale
            causal = jnp.arange(t)[None, :] <= pos[:, None]
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            o = jnp.einsum("gts,sge->tge", q(p), v)
            return jnp.einsum("tge,ged->td", q(o), q(o_w))

        return by_rows(block, ATTN_ROWS, c_q, positions)

    out, _ = lax.scan(lambda acc, ws: (acc + head_group(ws), None),
                      jnp.zeros((t, o_w.shape[-1]), jnp.float32), (q_b, k_b, v_b, o_w))
    return out


def route(h, ep, m, q):
    """Chosen ids (T, k) and their gates: softmax first, the bias moves the
    choice only, ``route_scale`` times the probability, no renormalisation."""
    p = jax.nn.softmax(jnp.einsum("td,de->te", q(h), q(f32(ep["router_weight"]))), axis=-1)
    _best, ids = lax.top_k(p + f32(ep["router_bias"]), int(m["experts_per_token"]))
    return ids, m["route_scale"] * jnp.take_along_axis(p, ids, axis=-1)


def experts(h, ep, m, q):
    """The expert layer for rows h: the held real experts' part and the
    identity experts' whole."""
    ids, gates = route(h, ep, m, q)
    held = jnp.asarray(m["held_experts"], jnp.int32)

    def one(y, xs):
        e, gate_w, up_w, down_w = xs
        g = jnp.sum(jnp.where(ids == e, gates, 0.0), axis=-1)
        return y + g[:, None] * swiglu(h, gate_w, up_w, down_w, q), None

    identity = jnp.sum(jnp.where(ids >= m["n_experts"], gates, 0.0), axis=-1)
    y, _ = lax.scan(one, identity[:, None] * h,
                    (held, ep["expert_gate_weight"], ep["expert_up_weight"],
                     ep["expert_down_weight"]))
    return y


def attention_half(x, sp, m, q):
    """x + MLA(N(x)) on a whole sequence."""
    positions = jnp.arange(x.shape[0])
    h = by_rows(lambda r: rmsnorm(r, sp["attn_norm"], m["norm_eps"]), ROWS, x)
    c_q, c_kv, k_rope = by_rows(lambda r, p: projections(r, p, sp, m, q), ROWS, h,
                                positions)
    return x + attention(c_q, c_kv, k_rope, positions, sp, m, q)


def first_sublayer(x, sp, ep, m, quant=EXACT):
    """x -> (x2, m): the first attention, then the expert layer and the first
    dense FFN from the same normed rows."""
    q = quant[0]
    x1 = attention_half(x, sp, m, q)

    def rows(r):
        h1 = rmsnorm(r, sp["ffn_norm"], m["norm_eps"])
        return (r + swiglu(h1, sp["dense_gate_weight"], sp["dense_up_weight"],
                           sp["dense_down_weight"], q), experts(h1, ep, m, q))

    return by_rows(rows, ROWS, x1)


def second_sublayer(x2, moe_out, sp, m, quant=EXACT):
    """(x2, m) -> x4: the second attention, the second dense FFN, and the
    expert layer's output added."""
    q = quant[0]
    x3 = attention_half(x2, sp, m, q)

    def rows(r, mo):
        h3 = rmsnorm(r, sp["ffn_norm"], m["norm_eps"])
        return r + swiglu(h3, sp["dense_gate_weight"], sp["dense_up_weight"],
                          sp["dense_down_weight"], q) + mo

    return by_rows(rows, ROWS, x3, moe_out)


def sublayer_leaves(params, l, a):
    return {k: params["%s.%d.%d" % (k, l, a)] for k in SUBLAYER_LEAVES}


def expert_leaves(params, l):
    return {k: params["%s.%d" % (k, l)] for k in MOE_LEAVES}


def _frozen(m):
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in m.items()))


@functools.lru_cache(maxsize=None)
def _jitted(quant, frozen):
    m = dict(frozen)
    return (jax.jit(lambda x, sp, ep: first_sublayer(x, sp, ep, m, quant)),
            jax.jit(lambda x, mo, sp: second_sublayer(x, mo, sp, m, quant)))


def hidden(params, tokens, m, quant=EXACT):
    """Final-RMSNorm output (T, d) for tokens (T,) int32.  Each half of a
    double layer is a program of its own, given that half's leaves alone."""
    first, second = _jitted(quant, _frozen(m))
    x = f32(jnp.take(params["embed_weight"], tokens, axis=0))
    for l in range(int(m["n_layers"])):
        x, moe_out = first(x, sublayer_leaves(params, l, 0), expert_leaves(params, l))
        x = second(x, moe_out, sublayer_leaves(params, l, 1))
    return rmsnorm(x, params["final_norm"], m["norm_eps"])


def head(params, x, quant=EXACT):
    """Next-token logits over the rows of the vocabulary held, of
    final-RMSNorm rows x (..., d)."""
    q = quant[0]
    return jnp.einsum("...d,vd->...v", q(x), q(f32(params["head_weight"])))


def logits(params, tokens, m, quant=EXACT):
    return head(params, hidden(params, tokens, m, quant), quant)
