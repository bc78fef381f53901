"""Plain reference for the Sarvam-105B decoder (``model_type: sarvam_mla``) as
one chip of an expert-parallel pool holds it.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``,
written from the equations of the configuration's sources (the catalog row's
``config``, DeepSeek-V3's latent attention, router and ``deepseek_yarn``
rotary scaling), not from the program: no cache, no kernels, no batching,
nothing of ``mxnet_tpu``.  One sequence at a time, every position attending
every earlier one.

A layer on input ``x``, ``N`` an RMSNorm (eps ``norm_eps``) with a gain of its
own wherever it stands, ``H`` heads:

- attention: ``h = N(x)``; ``q = h W_q`` (``d -> H x (d_nope + d_rope)``, a
  full-rank query), ``q = Nq(q)`` over each head's dims with one gain all heads
  share; ``[q_nope | q_rope] = q``; ``[c_kv | k_r] = h W_kva``,
  ``c_kv = N(c_kv)``, ``k_r = Nk(k_r)`` (both norms where ``qk_norm``); YaRN rotary on
  ``q_rope`` and on ``k_r``, which the heads share; ``k_nope = c_kv W_kb``,
  ``v = c_kv W_vb`` a head (``W_kvb = [W_kb | W_vb]``); scores ``(q_nope .
  k_nope + q_rope . k_rope) (d_nope + d_rope)^-1/2 mscale^2``, causal softmax
  over every earlier position, ``x + concat_h(P v) W_o``.
- YaRN (DeepSeek-V3's ``deepseek_yarn``): interleaved pairs; pair ``i`` of
  ``n / 2`` turns by ``position * f_i`` with ``f_i = e_i (1 - r_i) + (e_i /
  factor) r_i``, ``e_i = rope_theta^(-2i/n)`` and ``r_i`` a linear ramp from 0
  at the correction range's low end to 1 at its high end; the ends are the
  pairs that turn ``beta_fast`` (32) and ``beta_slow`` (1) times over
  ``original`` positions, floored and ceiled.  ``mscale = 0.1 mscale_all_dim ln(factor) +
  1`` squared multiplies the softmax scale; cos and sin are multiplied by the
  ratio of the ``mscale`` factor to the ``mscale_all_dim`` one.
- FFN on ``N(x)``: the leading ``n_dense_layers`` a dense SwiGLU ``(silu(h
  W_gate) * (h W_up)) W_down`` of width ``d_ff``; the others an expert layer:
  ``s = sigmoid(h W_r)`` over all ``n_experts``, the ``experts_per_token``
  largest of ``s + b`` chosen, ``g = route_scale s / sum(s chosen)``, output
  the shared expert's SwiGLU plus ``sum g_e SwiGLU_e(h)`` over the chosen
  experts **that this chip holds** (``held_experts``; the others lie on other
  chips and their part is left out, here as in the program).
- head: ``N``, then the untied head over the rows of the vocabulary held.

The parameter dict has the program's layout (``mxnet_tpu/models/mla_moe.py``
``param_shapes`` with ``q_rank`` 0, no indexer, ``qk_norm``: gains ``q_norm``, ``k_norm``):
attention leaves stacked over all layers, ``dense_*`` over the dense layers,
``router_*``, ``expert_*`` (held experts only, in the order of
``held_experts``) and ``shared_*`` over the expert layers.  Leaves may be
bfloat16: a layer's are widened where they are used, one layer and one
expert at a time.  Attention goes a group of heads, a block of query rows
and a block of keys at a time (online softmax, key blocks past a row
block's last position not made), the other products a block of rows at a
time, so that a 68 k-token sequence at the published widths fits one chip.

``quant`` puts a lower precision in the reference's place for the control: a
pair from ``benchmark/reference/precision.py`` whose first member rounds both
operands of every product (projections, attention scores and values, router,
experts, FFN, head).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference.precision import EXACT

ATTN_LEAVES = ("attn_norm", "ffn_norm", "q_weight", "q_norm", "kv_a_weight",
               "kv_a_norm", "k_norm", "kv_b_weight", "o_weight")
DENSE_LEAVES = ("dense_gate_weight", "dense_up_weight", "dense_down_weight")
MOE_LEAVES = ("router_weight", "router_bias", "expert_gate_weight", "expert_up_weight",
              "expert_down_weight", "shared_gate_weight", "shared_up_weight",
              "shared_down_weight")
ROWS = 2048          # rows a block of the per-token products
ATTN_ROWS = 512      # query rows a block of attention
KEYS = 4096          # keys a block of attention
HEAD_GROUP = 8       # heads a group of attention
# the rest of ``rope_scaling`` (the catalog row's, DeepSeek-V3's own)
BETA_FAST, BETA_SLOW, MSCALE, MSCALE_ALL_DIM = 32, 1, 1.0, 1.0


def f32(x):
    return x.astype(jnp.float32)


def rmsnorm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * f32(gamma)


def yarn_get_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(m, dim):
    """DeepSeek-V3's ``DeepseekV3YarnRotaryEmbedding`` frequencies, (dim / 2,)."""
    base, factor = float(m["rope_theta"]), float(m["yarn_factor"])
    original = float(m["yarn_original"])

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(BETA_FAST)), 0)
    high = min(math.ceil(correction_dim(BETA_SLOW)), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    extra_mask = 1.0 - ramp
    return jnp.asarray(freq_inter * (1 - extra_mask) + freq_extra * extra_mask, jnp.float32)


def softmax_scale(m):
    scale = (m["d_nope"] + m["d_rope"]) ** -0.5
    if m.get("yarn_factor") and MSCALE_ALL_DIM:
        scale *= yarn_get_mscale(m["yarn_factor"], MSCALE_ALL_DIM) ** 2
    return scale


def rotary(x, positions, m):
    """Interleaved pairs (x[2i], x[2i+1]) turned by positions * f_i; x is (T, n)
    or (T, heads, n)."""
    n = x.shape[-1]
    if m.get("yarn_factor"):
        inv = yarn_inv_freq(m, n)
        cos_scale = (yarn_get_mscale(m["yarn_factor"], MSCALE)
                     / yarn_get_mscale(m["yarn_factor"], MSCALE_ALL_DIM))
    else:
        inv = 1.0 / (m["rope_theta"] ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n))
        cos_scale = 1.0
    ang = f32(positions)[:, None] * inv
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang) * cos_scale, jnp.sin(ang) * cos_scale
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


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


def latent(h, positions, lp, m, q):
    """Per token: the normed c_kv and the (normed, rotated) shared k_rope."""
    kv = jnp.einsum("td,dr->tr", q(h), q(f32(lp["kv_a_weight"])))
    c_kv = rmsnorm(kv[:, :m["kv_rank"]], lp["kv_a_norm"], m["norm_eps"])
    k_r = kv[:, m["kv_rank"]:]
    if m.get("qk_norm"):
        k_r = rmsnorm(k_r, lp["k_norm"], m["norm_eps"])
    return c_kv, rotary(k_r, positions, m)


def attention(h, c_kv, k_rope, lp, m, q):
    """Causal softmax over every earlier position, a group of heads, a block
    of query rows and a block of keys at a time; returns concat_h(P v) W_o,
    (T, d)."""
    nope, dv, heads = m["d_nope"], m["d_v"], m["n_heads"]
    group = min(HEAD_GROUP, heads)
    scale = softmax_scale(m)
    t = h.shape[0]
    keys = min(KEYS, t)
    kpad = -t % keys
    positions = jnp.arange(t)

    def grouped(w, lead):
        """(..., heads, e) -> (heads / group, ..., group, e)"""
        w = f32(w).reshape(w.shape[:lead] + (heads // group, group) + w.shape[lead + 1:])
        return jnp.moveaxis(w, lead, 0)

    kv_b = grouped(lp["kv_b_weight"], 1)                     # (n, r, g, nope + dv)
    w_q, o_w = grouped(lp["q_weight"], 1), grouped(lp["o_weight"], 0)
    k_rope = jnp.pad(q(k_rope), ((0, kpad), (0, 0)))

    def head_group(ws):
        w_q, kv_b, o_w = ws
        k_nope = jnp.pad(q(jnp.einsum("sr,rge->sge", q(c_kv), q(kv_b[..., :nope]))),
                         ((0, kpad), (0, 0), (0, 0)))
        v = jnp.pad(q(jnp.einsum("sr,rge->sge", q(c_kv), q(kv_b[..., nope:]))),
                    ((0, kpad), (0, 0), (0, 0)))

        def block(h_rows, pos):
            qh = jnp.einsum("td,dge->tge", q(h_rows), q(w_q))
            if m.get("qk_norm"):
                qh = rmsnorm(qh, lp["q_norm"], m["norm_eps"])
            q_nope, q_rope = q(qh[..., :nope]), q(rotary(qh[..., nope:], pos, m))

            def key_block(j, state):
                top, norm, acc = state
                at = j * keys
                kn = lax.dynamic_slice_in_dim(k_nope, at, keys)
                kr = lax.dynamic_slice_in_dim(k_rope, at, keys)
                vv = lax.dynamic_slice_in_dim(v, at, keys)
                s = (jnp.einsum("tge,sge->gts", q_nope, kn)
                     + jnp.einsum("tge,se->gts", q_rope, kr)) * scale
                s = jnp.where((at + jnp.arange(keys))[None, None, :] <= pos[None, :, None],
                              s, -jnp.inf)
                new = jnp.maximum(top, s.max(axis=-1))
                p = jnp.exp(s - new[..., None])
                keep = jnp.exp(top - new)
                return (new, norm * keep + p.sum(axis=-1),
                        acc * keep[..., None] + jnp.einsum("gts,sge->gte", q(p), vv))

            # the first key block holds position 0, so every row's maximum is
            # finite from there on; a block's last real row is its largest
            # position (the padded rows of the last block are at 0)
            state = (jnp.full((group, pos.shape[0]), -jnp.inf, jnp.float32),
                     jnp.zeros((group, pos.shape[0]), jnp.float32),
                     jnp.zeros((group, pos.shape[0], dv), jnp.float32))
            _top, norm, acc = lax.fori_loop(0, jnp.max(pos) // keys + 1, key_block,
                                            state)
            o = (acc / norm[..., None]).transpose(1, 0, 2)         # (t, g, dv)
            return jnp.einsum("tge,ged->td", q(o), q(o_w))

        return by_rows(block, ATTN_ROWS, h, positions)

    out, _ = lax.scan(lambda acc, ws: (acc + head_group(ws), None),
                      jnp.zeros((t, o_w.shape[-1]), jnp.float32), (w_q, kv_b, o_w))
    return out


def experts(h, lp, m, q):
    """The expert layer for normed rows h: the shared expert and the held
    routed experts' part."""
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", q(h), q(f32(lp["router_weight"]))))
    _best, ids = lax.top_k(s + f32(lp["router_bias"]), int(m["experts_per_token"]))
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    gates = m["route_scale"] * chosen / chosen.sum(axis=-1, keepdims=True)
    held = jnp.asarray(m["held_experts"], jnp.int32)

    def one(y, xs):
        e, gate_w, up_w, down_w = xs
        g = jnp.sum(jnp.where(ids == e, gates, 0.0), axis=-1)
        return y + g[:, None] * swiglu(h, gate_w, up_w, down_w, q), None

    shared = swiglu(h, lp["shared_gate_weight"], lp["shared_up_weight"],
                    lp["shared_down_weight"], q)
    y, _ = lax.scan(one, shared, (held, lp["expert_gate_weight"], lp["expert_up_weight"],
                                  lp["expert_down_weight"]))
    return y


def layer(x, lp, m, quant=EXACT):
    """x -> x + attention, then + the FFN (dense where ``lp`` has the dense
    leaves, else the expert layer)."""
    q = quant[0]
    positions = jnp.arange(x.shape[0])
    h = by_rows(lambda r: rmsnorm(r, lp["attn_norm"], m["norm_eps"]), ROWS, x)
    c_kv, k_rope = by_rows(lambda r, p: latent(r, p, lp, m, q), ROWS, h, positions)
    x = x + attention(h, c_kv, k_rope, lp, m, q)

    def rows(r):
        hf = rmsnorm(r, lp["ffn_norm"], m["norm_eps"])
        if "dense_gate_weight" in lp:
            return r + swiglu(hf, lp["dense_gate_weight"], lp["dense_up_weight"],
                              lp["dense_down_weight"], q)
        return r + experts(hf, lp, m, q)

    return by_rows(rows, ROWS, x)


def layer_leaves(params, m, i):
    lp = {k: params[k][i] for k in ATTN_LEAVES if k in params}
    dense = int(m["n_dense_layers"])
    if i < dense:
        lp.update({k: params[k][i] for k in DENSE_LEAVES})
    else:
        lp.update({k: params[k][i - dense] for k in MOE_LEAVES})
    return lp


def _frozen(m):
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in m.items()))


@functools.lru_cache(maxsize=None)
def _jitted(quant, frozen):
    m = dict(frozen)
    return jax.jit(lambda x, lp: layer(x, lp, m, quant))


def hidden(params, tokens, m, quant=EXACT):
    """Final-RMSNorm output (T, d) for tokens (T,) int32.  Each layer is one
    program, given that layer's leaves alone."""
    run = _jitted(quant, _frozen(m))
    x = f32(jnp.take(params["embed_weight"], tokens, axis=0))
    for i in range(int(m["n_layers"])):
        x = run(x, layer_leaves(params, m, i))
    return rmsnorm(x, params["final_norm"], m["norm_eps"])


def head(params, x, quant=EXACT):
    """Next-token logits over the rows of the vocabulary held, of
    final-RMSNorm rows x (..., d)."""
    q = quant[0]
    return jnp.einsum("...d,vd->...v", q(x), q(f32(params["head_weight"])))


def logits(params, tokens, m, quant=EXACT):
    return head(params, hidden(params, tokens, m, quant), quant)
