"""Plain reference for the GLM-5 decoder (``model_type: glm_moe_dsa``) as one
chip of an expert-parallel pool holds it.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``,
written from the equations of the configuration's sources, not from the
program: no cache, no kernels, no batching, nothing of ``mxnet_tpu``.  One
sequence at a time, every position attending causally.

A block, with ``h`` the RMSNorm (eps ``norm_eps``) of the half's input:

- latent attention (DeepSeek-V2/V3): ``c_q = RMSNorm(h W_qa)``;
  ``[q_nope | q_rope] = c_q W_qb`` a head; ``[c_kv | k_rope] = h W_kva``,
  ``c_kv = RMSNorm(c_kv)``; interleaved rotary (``rope_theta``) on ``q_rope``
  and on ``k_rope``, which the heads share; ``[k_nope | v] = c_kv W_kvb`` a
  head; scores ``(q_nope . k_nope + q_rope . k_rope) / sqrt(d_nope + d_rope)``,
  softmax over the allowed keys, ``concat_h(P v) W_o``;
- the indexer (DeepSeek-V3.2) says which keys are allowed:
  ``q_I = c_q W_Iq`` (``index_heads`` x ``index_dim``),
  ``k_I = LayerNorm(h W_Ik)`` (eps ``index_norm_eps``), rotary on the first
  ``index_rope_dim`` of both, ``w = h W_Iw * index_heads^-1/2 * index_dim^-1/2``,
  ``I[t, s] = sum_h w[t, h] relu(q_I[t, h] . k_I[s])``; position ``t`` attends
  the ``index_topk`` causal positions of largest ``I`` (all, while there are
  no more than that; of equal scores the earlier position first);
- experts (DeepSeek-V3 ``noaux_tc``, one group), after ``n_dense_layers``
  leading layers with one dense SwiGLU: ``s = sigmoid(h W_r)``; the
  ``experts_per_token`` largest of ``s + b`` are chosen; ``g = route_scale *
  s / sum(s chosen)``; the output is ``sum g_e SwiGLU_e(h)`` over the chosen
  experts **that this chip holds** (``held_experts``; the others lie on other
  chips and their part is left out, here as in the program) plus the shared
  expert's ``SwiGLU(h)``;
- head: RMSNorm, then the untied head over the rows of the vocabulary held.

Departures from the source, the program's too (the configuration file lists
them): no Hadamard rotation of ``q_I`` and ``k_I`` (orthogonal, so the scores
are the same), no multi-token-prediction layer.

The parameter dict has the program's layout (``mxnet_tpu/models/mla_moe.py``
``param_shapes``): attention and indexer leaves stacked over all layers,
``dense_*`` over the dense layers, ``router_*``, ``expert_*`` (held experts
only, in the order of ``held_experts``) and ``shared_*`` over the layers with
experts.  Leaves may be bfloat16: a layer's are widened where they are used,
one layer and one expert at a time; attention goes a group of heads and a
block of rows at a time, the other products a block of rows at a time, so
that a 19 k-token sequence at the published widths fits one chip.

``quant`` puts a lower precision in the reference's place for the control: a
pair from ``benchmark/reference/precision.py`` whose first member rounds both
operands of every product (projections, index scores, attention scores and
values, router, experts, head).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.precision import EXACT

ATTN_LEAVES = ("attn_norm", "ffn_norm", "q_a_weight", "q_a_norm", "q_b_weight",
               "kv_a_weight", "kv_a_norm", "kv_b_weight", "o_weight",
               "index_q_weight", "index_k_weight", "index_k_norm_gamma",
               "index_k_norm_beta", "index_w_weight")
DENSE_LEAVES = ("dense_gate_weight", "dense_up_weight", "dense_down_weight")
MOE_LEAVES = ("router_weight", "router_bias", "expert_gate_weight",
              "expert_up_weight", "expert_down_weight", "shared_gate_weight",
              "shared_up_weight", "shared_down_weight")
ROWS = 2048          # rows a block of the per-token products
ATTN_ROWS = 256      # query rows a block of attention
HEAD_GROUP = 8       # heads a group of attention


def f32(x):
    return x.astype(jnp.float32)


def rmsnorm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * f32(gamma)


def layernorm(x, gamma, beta, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * f32(gamma) + f32(beta)


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


def projections(h, positions, lp, m, q):
    """Per token: c_q, c_kv, k_rope (rotated), q_I and k_I (rotated), w."""
    c_q = rmsnorm(jnp.einsum("td,dr->tr", q(h), q(f32(lp["q_a_weight"]))),
                  lp["q_a_norm"], m["norm_eps"])
    kv = jnp.einsum("td,dr->tr", q(h), q(f32(lp["kv_a_weight"])))
    c_kv = rmsnorm(kv[:, :m["kv_rank"]], lp["kv_a_norm"], m["norm_eps"])
    k_rope = rotary(kv[:, m["kv_rank"]:], positions, m["rope_theta"])
    r = m["index_rope_dim"]
    q_i = jnp.einsum("tr,rhe->the", q(c_q), q(f32(lp["index_q_weight"])))
    q_i = jnp.concatenate([rotary(q_i[..., :r], positions, m["rope_theta"]),
                           q_i[..., r:]], axis=-1)
    k_i = layernorm(jnp.einsum("td,de->te", q(h), q(f32(lp["index_k_weight"]))),
                    lp["index_k_norm_gamma"], lp["index_k_norm_beta"],
                    m["index_norm_eps"])
    k_i = jnp.concatenate([rotary(k_i[:, :r], positions, m["rope_theta"]),
                           k_i[:, r:]], axis=-1)
    w = jnp.einsum("td,dh->th", q(h), q(f32(lp["index_w_weight"]))) \
        * (m["index_heads"] ** -0.5 * m["index_dim"] ** -0.5)
    return c_q, c_kv, k_rope, q_i, k_i, w


def allowed_keys(q_i, k_i, w, m, q):
    """(T, T) bool: for each position the causal positions it may attend, the
    ``index_topk`` of largest index score."""
    t = k_i.shape[0]
    k = min(int(m["index_topk"]), t)
    k_i = q(k_i)

    def block(q_rows, w_rows, pos):
        scores = jnp.einsum("the,se->ths", q(q_rows), k_i)
        index = jnp.sum(jax.nn.relu(scores) * w_rows[:, :, None], axis=1)
        causal = jnp.arange(t)[None, :] <= pos[:, None]
        index = jnp.where(causal, index, -jnp.inf)
        least = lax.top_k(index, k)[0][:, -1:]          # the k-th largest score
        above, equal = index > least, index == least
        # of equal scores at the cut the earlier positions, as many as fit
        room = k - jnp.sum(above, axis=-1, keepdims=True)
        return causal & (above | (equal & (jnp.cumsum(equal, axis=-1) <= room)))

    return by_rows(block, ATTN_ROWS, q_i, w, jnp.arange(t))


def attention(c_q, c_kv, k_rope, allowed, positions, lp, m, q):
    """softmax over the allowed keys, a group of heads and a block of query
    rows at a time; returns concat_h(P v) W_o, (T, d)."""
    nope, dv = m["d_nope"], m["d_v"]
    heads = m["n_heads"]
    group = min(HEAD_GROUP, heads)
    scale = (nope + m["d_rope"]) ** -0.5
    q_b = f32(lp["q_b_weight"]).reshape(lp["q_b_weight"].shape[0], heads // group, group, -1)
    kv_b = f32(lp["kv_b_weight"]).reshape(lp["kv_b_weight"].shape[0], heads // group, group, -1)
    o_w = f32(lp["o_weight"]).reshape(heads // group, group, dv, -1)
    k_rope = q(k_rope)

    def head_group(ws):
        q_b, kv_b, o_w = ws                      # (r, g, e) (r, g, e) (g, dv, d)
        kv = jnp.einsum("sr,rge->sge", q(c_kv), q(kv_b))
        k_nope, v = q(kv[..., :nope]), q(kv[..., nope:])

        def block(cq_rows, ok, pos):
            qh = jnp.einsum("tr,rge->tge", q(cq_rows), q(q_b))
            q_nope = q(qh[..., :nope])
            q_rope = q(rotary(qh[..., nope:], pos, m["rope_theta"]))
            s = (jnp.einsum("tge,sge->gts", q_nope, k_nope)
                 + jnp.einsum("tge,se->gts", q_rope, k_rope)) * scale
            p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
            o = jnp.einsum("gts,sge->tge", q(p), v)
            return jnp.einsum("tge,ged->td", q(o), q(o_w))

        return by_rows(block, ATTN_ROWS, c_q, allowed, positions)

    out, _ = lax.scan(lambda acc, ws: (acc + head_group(ws), None),
                      jnp.zeros((c_q.shape[0], o_w.shape[-1]), jnp.float32),
                      (q_b.transpose(1, 0, 2, 3), kv_b.transpose(1, 0, 2, 3), o_w))
    return out


def experts(h, lp, m, q):
    """The routed experts held here plus the shared expert, for rows h."""
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", q(h), q(f32(lp["router_weight"]))))
    _best, ids = lax.top_k(s + f32(lp["router_bias"]), int(m["experts_per_token"]))
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    gates = m["route_scale"] * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    held = jnp.asarray(m["held_experts"], jnp.int32)

    def one(y, xs):
        e, gate_w, up_w, down_w = xs
        g = jnp.sum(jnp.where(ids == e, gates, 0.0), axis=-1)
        return y + g[:, None] * swiglu(h, gate_w, up_w, down_w, q), None

    y = swiglu(h, lp["shared_gate_weight"], lp["shared_up_weight"],
               lp["shared_down_weight"], q)
    y, _ = lax.scan(one, y, (held, lp["expert_gate_weight"], lp["expert_up_weight"],
                             lp["expert_down_weight"]))
    return y


def block(x, lp, m, quant=EXACT):
    """One decoder block on a whole sequence x (T, d) float32; ``lp`` holds
    the layer's leaves (dense or expert kind by what is there)."""
    q = quant[0]
    positions = jnp.arange(x.shape[0])
    h = by_rows(lambda r: rmsnorm(r, lp["attn_norm"], m["norm_eps"]), ROWS, x)
    c_q, c_kv, k_rope, q_i, k_i, w = by_rows(
        lambda r, p: projections(r, p, lp, m, q), ROWS, h, positions)
    allowed = allowed_keys(q_i, k_i, w, m, q)
    x = x + attention(c_q, c_kv, k_rope, allowed, positions, lp, m, q)

    def ffn(rows):
        h = rmsnorm(rows, lp["ffn_norm"], m["norm_eps"])
        if "router_weight" in lp:
            return rows + experts(h, lp, m, q)
        return rows + swiglu(h, lp["dense_gate_weight"], lp["dense_up_weight"],
                             lp["dense_down_weight"], q)

    return by_rows(ffn, ROWS, x)


def layer_leaves(params, i, m):
    """The leaves of layer ``i`` alone."""
    lp = {k: params[k][i] for k in ATTN_LEAVES}
    dense = int(m["n_dense_layers"])
    lp.update({k: params[k][i] for k in DENSE_LEAVES} if i < dense
              else {k: params[k][i - dense] for k in MOE_LEAVES})
    return lp


@functools.lru_cache(maxsize=None)
def _jitted_block(quant, frozen):
    m = dict(frozen)
    return jax.jit(lambda x, lp: block(x, lp, m, quant))


def hidden(params, tokens, m, quant=EXACT):
    """Final-RMSNorm output (T, d) for tokens (T,) int32.  Each layer is a
    program of its own, given that layer's leaves alone."""
    frozen = tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                          for k, v in m.items()))
    step = _jitted_block(quant, frozen)
    x = f32(jnp.take(params["embed_weight"], tokens, axis=0))
    for i in range(int(m["n_layers"])):
        x = step(x, layer_leaves(params, i, m))
    return rmsnorm(x, params["final_norm"], m["norm_eps"])


def head(params, x, quant=EXACT):
    """Next-token logits over the rows of the vocabulary held, of
    final-RMSNorm rows x (..., d)."""
    q = quant[0]
    return jnp.einsum("...d,vd->...v", q(x), q(f32(params["head_weight"])))


def logits(params, tokens, m, quant=EXACT):
    return head(params, hidden(params, tokens, m, quant), quant)
