"""Plain reference for the Hy4-preview decoder (``model_type: hy_v4``) as one
chip of an expert-parallel pool holds it.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``,
written from the equations of the configuration's sources (the catalog row's
``config``; Hyper-Connections, arXiv:2409.19606, in the manifold-constrained
form of DeepSeek-AI's mHC; Gated Attention, arXiv:2505.06708; DeepSeek-V3.2's
indexer with IndexCache's cross-layer reuse; DeepSeek-V3's latent attention
and router), not from the program: no cache, no kernels, no batching,
nothing of ``mxnet_tpu``.  One sequence at a time.

A token's state is ``n = hc_mult`` streams ``X`` (n, d), each the token's
embedding at the start.  ``N`` is an RMSNorm (eps ``norm_eps``) with a gain
of its own wherever it stands.  Around every sublayer ``F``:

- the hyper-connection: ``v = vec(X) / rms(vec(X))`` (over n d, no gain),
  ``a = v phi`` (n (n + 2) outputs), ``H_pre = sigmoid(alpha_0 a[0:n] +
  b[0:n])``, ``H_post = hc_magnitude sigmoid(alpha_1 a[n:2n] + b[n:2n])``,
  ``H_res = SK(exp(alpha_2 mat(a[2n:]) + b[2n:]))`` where ``mat`` lays the
  n x n values out row by row and ``SK`` repeats ``SINKHORN_ITERS`` times
  ``M /= row sums + hc_eps; M /= column sums + hc_eps``; then ``u = sum_i
  H_pre[i] X[i]``, ``y = F(u)`` and ``X[i] <- sum_j H_res[i, j] X[j] +
  H_post[i] y``.
- attention ``F(u)``: ``h = N(u)``; ``c_q = N(h W_qa)``, ``[q_nope | q_rope] =
  c_q W_qb`` a head; ``[c_kv | k_r] = h W_kva``, ``c_kv = N(c_kv)``;
  interleaved rotary (``rope_theta``) on ``q_rope`` and on the one ``k_r``
  the heads share; ``[k_nope | v] = c_kv W_kvb`` a head; a **full** layer's
  indexer: ``k_I = LayerNorm(h W_Ik)`` (eps ``index_norm_eps``), ``q_I =
  c_q W_Iq``, rotary on the first ``index_rope_dim`` of both, ``w = h W_Iw
  index_heads^-1/2 index_dim^-1/2``, ``I[t, s] = sum_h w[t, h] relu(q_I[t,
  h] . k_I[s])``, and position ``t`` attends ``S(t)``, its ``index_topk``
  causal positions of largest ``I`` (``lax.top_k``; all of them while there
  are no more); a **shared** layer has no indexer and attends the ``S(t)``
  of the nearest earlier full layer; scores ``z = (q_nope . k_nope + q_rope .
  k_rope) (d_nope + d_rope)^-1/2``; ``p = exp(z) / (exp(sink_h) + sum_S
  exp(z))`` (``attn_sink``; without it the plain softmax); ``o_h = sum p v``;
  ``g = sigmoid(h W_g)`` (``attn_gate``); ``F = sum_h (o_h * g_h) W_o[h]``.
- FFN ``F(u)``: ``h = N(u)``; every SwiGLU is ``(silu(min(h W_g, L)) *
  clip(h W_u, -L, L)) W_d`` with ``L = swiglu_limit`` (no clamp where it is
  0); the leading ``n_dense_layers`` a dense SwiGLU of width ``d_ff``; the
  others ``s = sigmoid(h W_r)`` over all ``n_experts``, the
  ``experts_per_token`` largest of ``s + b`` chosen, ``g = route_scale s /
  sum(s chosen)``, output the shared expert's SwiGLU plus ``sum g_e
  SwiGLU_e(h)`` over the chosen experts **that this chip holds**
  (``held_experts``; the others lie on other chips and their part is left
  out, here as in the program).
- head: ``z = sum_i X[i]``, ``N(z)``, then the untied head over the rows of
  the vocabulary held, in float32.

The parameter dict has the program's layout (``mxnet_tpu/models/mla_moe.py``
``param_shapes``): attention, gate, sink and hyper-connection leaves stacked
over all layers, the indexer's over the full layers, ``dense_*`` over the
dense layers, ``router_*``, ``expert_*`` (held experts only, in the order of
``held_experts``) and ``shared_*`` over the expert layers.  Leaves may be
bfloat16: a layer's are widened where they are used.  Each layer is one
program whose streams are updated where they lie, a block of rows at a
time; attention goes a group of heads, a block of query rows and a block of
keys at a time (online softmax from the sink, key blocks past a row block's
last position not made), the index scores a block of rows and a block of
keys at a time, so that a 36 k-token sequence at the published widths fits
one chip once the program's state is freed.

``quant`` puts a lower precision in the reference's place for the control: a
pair from ``benchmark/reference/precision.py`` whose first member rounds both
operands of every product (hyper-connection projections, attention and
index projections and scores, values, gate, router, experts, FFN, head).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.precision import EXACT

ATTN_LEAVES = ("attn_norm", "ffn_norm", "q_a_weight", "q_a_norm", "q_b_weight",
               "kv_a_weight", "kv_a_norm", "kv_b_weight", "o_weight", "o_gate_weight",
               "attn_sink", "hc_attn_proj", "hc_attn_bias", "hc_attn_scale",
               "hc_ffn_proj", "hc_ffn_bias", "hc_ffn_scale")
INDEX_LEAVES = ("index_q_weight", "index_k_weight", "index_k_norm_gamma",
                "index_k_norm_beta", "index_w_weight")
DENSE_LEAVES = ("dense_gate_weight", "dense_up_weight", "dense_down_weight")
MOE_LEAVES = ("router_weight", "router_bias", "expert_gate_weight", "expert_up_weight",
              "expert_down_weight", "shared_gate_weight", "shared_up_weight",
              "shared_down_weight")
ROWS = 1024          # rows a block of the per-token products
ATTN_ROWS = 128      # query rows a block of attention and of the index scores
KEYS = 4096          # keys a block of attention and of the index scores
HEAD_GROUP = 4       # heads a group of attention
SINKHORN_ITERS = 20  # the assumed turns of the Sinkhorn projection (mHC's)


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
    """``fn`` over blocks of rows of the arrays, its results written into
    arrays of all rows a block at a time (no padded copy of the inputs: the
    last block is as long as what is left)."""
    t = arrays[0].shape[0]
    block = min(block, t)

    def one(start, size, outs):
        got = fn(*(lax.dynamic_slice_in_dim(a, start, size) for a in arrays))
        return jax.tree.map(lambda o, r: lax.dynamic_update_slice_in_dim(o, r, start, 0),
                            outs, got)

    shapes = jax.eval_shape(fn, *(a[:block] for a in arrays))
    outs = jax.tree.map(lambda x: jnp.zeros((t,) + x.shape[1:], x.dtype), shapes)
    whole = t // block
    outs = lax.fori_loop(0, whole, lambda j, outs: one(j * block, block, outs), outs)
    return one(whole * block, t % block, outs) if t % block else outs


def in_place(fn, block, x, *rest):
    """``x`` with each block of its rows replaced by ``fn(x rows, rest
    rows...)``, a block at a time, written where it lies (no second ``x``)."""
    t = x.shape[0]
    block = min(block, t)

    def one(start, size, x):
        def rows(a):
            return lax.dynamic_slice_in_dim(a, start, size)
        return lax.dynamic_update_slice_in_dim(x, fn(rows(x), *map(rows, rest)), start, 0)

    whole = t // block
    x = lax.fori_loop(0, whole, lambda j, x: one(j * block, block, x), x)
    return one(whole * block, t % block, x) if t % block else x


def swiglu(h, gate, up, down, m, q):
    g = jnp.einsum("td,df->tf", q(h), q(f32(gate)))
    u = jnp.einsum("td,df->tf", q(h), q(f32(up)))
    limit = m.get("swiglu_limit") or 0.0
    if limit:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return jnp.einsum("tf,fd->td", q(jax.nn.silu(g) * u), q(f32(down)))


# -- hyper-connections -------------------------------------------------------
def sinkhorn(m_, iters, eps):
    for _ in range(iters):
        m_ = m_ / (m_.sum(axis=-1, keepdims=True) + eps)
        m_ = m_ / (m_.sum(axis=-2, keepdims=True) + eps)
    return m_


def hc_maps(xs, lp, half, m, q):
    """Of streams ``xs`` (rows, n, d): ``H_pre`` (rows, n), ``H_post`` (rows,
    n) and ``H_res`` (rows, n, n) of sublayer ``half``'s connection."""
    rows, n, d = xs.shape
    v = xs.reshape(rows, n * d)
    v = v * lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True) + m["norm_eps"])
    a = jnp.einsum("tk,km->tm", q(v), q(f32(lp["hc_%s_proj" % half])))
    alpha, b = f32(lp["hc_%s_scale" % half]), f32(lp["hc_%s_bias" % half])
    pre = jax.nn.sigmoid(alpha[0] * a[:, :n] + b[:n])
    post = m["hc_magnitude"] * jax.nn.sigmoid(alpha[1] * a[:, n:2 * n] + b[n:2 * n])
    res = sinkhorn(jnp.exp(alpha[2] * a[:, 2 * n:] + b[2 * n:]).reshape(rows, n, n),
                   SINKHORN_ITERS, m["hc_eps"])
    return pre, post, res


def hc_read(xs, pre):
    """``u = sum_i H_pre[i] X[i]``."""
    return jnp.einsum("tn,tnd->td", pre, xs)


def hc_write(xs, y, post, res):
    """``X[i] <- sum_j H_res[i, j] X[j] + H_post[i] y``."""
    return jnp.einsum("tij,tjd->tid", res, xs) + post[:, :, None] * y[:, None, :]


# -- attention ---------------------------------------------------------------
def projections(h, positions, lp, m, q):
    """Per token: c_q, c_kv, k_rope (rotated)."""
    c_q = rmsnorm(jnp.einsum("td,dr->tr", q(h), q(f32(lp["q_a_weight"]))),
                  lp["q_a_norm"], m["norm_eps"])
    kv = jnp.einsum("td,dr->tr", q(h), q(f32(lp["kv_a_weight"])))
    c_kv = rmsnorm(kv[:, :m["kv_rank"]], lp["kv_a_norm"], m["norm_eps"])
    k_rope = rotary(kv[:, m["kv_rank"]:], positions, m["rope_theta"])
    return c_q, c_kv, k_rope


def indexer(h, c_q, positions, lp, m, q):
    """A full layer's q_I and k_I (rotated) and head weights w."""
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
    return q_i, k_i, w


def select(q_i, k_i, w, m, q):
    """``S(t)`` of every position: (T, k) ids of its ``index_topk`` causal
    positions of largest index score, and (T, k) which of them are causal
    (a row with fewer causal positions fills up with others)."""
    t = k_i.shape[0]
    k = min(int(m["index_topk"]), t)
    keys = min(KEYS, t)
    blocks = jnp.pad(q(k_i), ((0, -t % keys), (0, 0))).reshape(-1, keys, k_i.shape[1])

    def block(q_rows, w_rows, pos):
        def scores(k_block):
            s = jnp.einsum("the,se->ths", q(q_rows), k_block)
            return jnp.sum(jax.nn.relu(s) * w_rows[:, :, None], axis=1)

        index = jnp.moveaxis(lax.map(scores, blocks), 0, 1).reshape(pos.shape[0], -1)[:, :t]
        index = jnp.where(jnp.arange(t)[None, :] <= pos[:, None], index, -jnp.inf)
        top, ids = lax.top_k(index, k)
        return ids, top > -jnp.inf

    return by_rows(block, ATTN_ROWS, q_i, w, jnp.arange(t))


def attention(normed, xs, c_q, c_kv, k_rope, ids, ok, lp, m, q):
    """Each position over its selected keys, a group of heads, a block of
    query rows and a block of keys at a time; returns ``sum_h (o_h * g_h)
    W_o[h]``, (T, d).  The gate's input ``h`` of a block of rows is
    ``normed(streams of those rows)``, made again where it is used rather
    than kept for every position."""
    nope, dv, heads = m["d_nope"], m["d_v"], m["n_heads"]
    group = min(HEAD_GROUP, heads)
    scale = (nope + m["d_rope"]) ** -0.5
    t, d = c_q.shape[0], xs.shape[-1]
    keys = min(KEYS, t)
    tpad = t + (-t % keys)

    def grouped(w, lead):
        """(..., heads, e) -> (heads / group, ..., group, e), as stored"""
        w = w.reshape(w.shape[:lead] + (heads // group, group) + w.shape[lead + 1:])
        return jnp.moveaxis(w, lead, 0)

    ws = {"q_b": grouped(lp["q_b_weight"], 1), "kv_b": grouped(lp["kv_b_weight"], 1),
          "o": grouped(lp["o_weight"], 0)}
    if m.get("attn_gate"):
        ws["gate"] = grouped(lp["o_gate_weight"].reshape(d, heads, dv), 1)
    if m.get("attn_sink"):
        ws["sink"] = grouped(lp["attn_sink"], 0)
    # keys padded to whole blocks (the padding is never allowed)
    k_rope = jnp.pad(q(k_rope), ((0, tpad - t), (0, 0)))
    c_kv = jnp.pad(q(c_kv), ((0, tpad - t), (0, 0)))

    def head_group(out, w):
        kv = jnp.einsum("sr,rge->sge", c_kv, q(f32(w["kv_b"])))
        k_nope, v = q(kv[..., :nope]), q(kv[..., nope:])

        def block(out_rows, cq_rows, x_rows, id_rows, ok_rows, pos):
            rows = pos.shape[0]
            allowed = jnp.zeros((rows, tpad), bool).at[
                jnp.arange(rows)[:, None], id_rows].set(ok_rows)
            qh = jnp.einsum("tr,rge->tge", q(cq_rows), q(f32(w["q_b"])))
            q_nope, q_rope = q(qh[..., :nope]), q(rotary(qh[..., nope:], pos,
                                                         m["rope_theta"]))

            def key_block(j, state):
                top, norm, acc = state
                at = j * keys
                s = (jnp.einsum("tge,sge->gts", q_nope,
                                lax.dynamic_slice_in_dim(k_nope, at, keys))
                     + jnp.einsum("tge,se->gts", q_rope,
                                  lax.dynamic_slice_in_dim(k_rope, at, keys))) * scale
                s = jnp.where(lax.dynamic_slice_in_dim(allowed, at, keys, axis=1)[None],
                              s, -jnp.inf)
                new = jnp.maximum(top, s.max(axis=-1))
                # no selected key met yet: nothing to shift by
                shift = jnp.where(new == -jnp.inf, 0.0, new)
                p = jnp.exp(s - shift[..., None])
                keep = jnp.exp(top - shift)
                pv = jnp.einsum("gts,sge->gte", q(p),
                                lax.dynamic_slice_in_dim(v, at, keys))
                return new, norm * keep + p.sum(axis=-1), acc * keep[..., None] + pv

            if "sink" in w:       # a key of logit sink_h and no value
                state = (jnp.broadcast_to(f32(w["sink"])[:, None], (group, rows)),
                         jnp.ones((group, rows), jnp.float32))
            else:
                state = (jnp.full((group, rows), -jnp.inf, jnp.float32),
                         jnp.zeros((group, rows), jnp.float32))
            state += (jnp.zeros((group, rows, dv), jnp.float32),)
            _top, norm, acc = lax.fori_loop(0, jnp.max(pos) // keys + 1, key_block, state)
            o = (acc / norm[..., None]).transpose(1, 0, 2)           # (t, g, dv)
            if "gate" in w:
                o = o * jax.nn.sigmoid(jnp.einsum("td,dge->tge", q(normed(x_rows)),
                                                  q(f32(w["gate"]))))
            return out_rows + jnp.einsum("tge,ged->td", q(o), q(f32(w["o"])))

        return in_place(block, ATTN_ROWS, out, c_q, xs, ids, ok, jnp.arange(t)), None

    out, _ = lax.scan(head_group, jnp.zeros((t, d), jnp.float32), ws)
    return out


# -- FFN ---------------------------------------------------------------------
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
        return y + g[:, None] * swiglu(h, gate_w, up_w, down_w, m, q), None

    shared = swiglu(h, lp["shared_gate_weight"], lp["shared_up_weight"],
                    lp["shared_down_weight"], m, q)
    y, _ = lax.scan(one, shared, (held, lp["expert_gate_weight"], lp["expert_up_weight"],
                                  lp["expert_down_weight"]))
    return y


def ffn(u, lp, m, q):
    h = rmsnorm(u, lp["ffn_norm"], m["norm_eps"])
    if "dense_gate_weight" in lp:
        return swiglu(h, lp["dense_gate_weight"], lp["dense_up_weight"],
                      lp["dense_down_weight"], m, q)
    return experts(h, lp, m, q)


# -- the layer ---------------------------------------------------------------
def layer(xs, ids, ok, lp, m, quant=EXACT):
    """One layer on the streams ``xs`` (T, n, d) float32; ``ids``/``ok`` the
    nearest earlier full layer's selection (ignored where this layer has its
    own indexer, ``index_q_weight`` in ``lp``).  Returns the new streams and
    the selection this layer attended."""
    q = quant[0]
    t = xs.shape[0]
    positions = jnp.arange(t)
    own = "index_q_weight" in lp

    def normed(x_rows):
        """The attention's input h of these rows, and H_post, H_res."""
        pre, post, res = hc_maps(x_rows, lp, "attn", m, q)
        return rmsnorm(hc_read(x_rows, pre), lp["attn_norm"], m["norm_eps"]), post, res

    def attn_in(x_rows, pos):
        h, post, res = normed(x_rows)
        c_q, c_kv, k_rope = projections(h, pos, lp, m, q)
        index = indexer(h, c_q, pos, lp, m, q) if own else ()
        return (post, res, c_q, c_kv, k_rope) + index

    post, res, c_q, c_kv, k_rope, *index = by_rows(attn_in, ROWS, xs, positions)
    if own:
        ids, ok = select(*index, m, q)
        del index
    y = attention(lambda x_rows: normed(x_rows)[0], xs, c_q, c_kv, k_rope, ids, ok,
                  lp, m, q)
    xs = in_place(hc_write, ROWS, xs, y, post, res)

    def ffn_rows(x_rows):
        pre, post, res = hc_maps(x_rows, lp, "ffn", m, q)
        return hc_write(x_rows, ffn(hc_read(x_rows, pre), lp, m, q), post, res)

    return in_place(ffn_rows, ROWS, xs), ids, ok


def full_layers(m):
    types = m.get("indexer_types") or ["full"] * int(m["n_layers"])
    return [i for i, kind in enumerate(types) if kind == "full"]


def layer_leaves(params, m, i):
    """The leaves of layer ``i`` alone (the indexer's where it has its own)."""
    lp = {k: params[k][i] for k in ATTN_LEAVES if k in params}
    full = full_layers(m)
    if i in full:
        lp.update({k: params[k][full.index(i)] for k in INDEX_LEAVES})
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
def _jitted(quant, frozen, own):
    m = dict(frozen)
    if own:
        return jax.jit(lambda xs, lp: layer(xs, None, None, lp, m, quant),
                       donate_argnums=(0,))
    return jax.jit(lambda xs, ids, ok, lp: layer(xs, ids, ok, lp, m, quant),
                   donate_argnums=(0,))


def hidden(params, tokens, m, quant=EXACT):
    """``N(sum_i X[i])`` (T, d) after the last layer, for tokens (T,) int32.
    Each layer is one program, given that layer's leaves alone; a shared
    layer is handed the selection of the full layer before it."""
    n = int(m["hc_mult"])
    x = f32(jnp.take(params["embed_weight"], tokens, axis=0))
    xs = jnp.broadcast_to(x[:, None], (x.shape[0], n, x.shape[1]))
    del x
    ids = ok = None
    full = full_layers(m)
    for i in range(int(m["n_layers"])):
        lp = layer_leaves(params, m, i)
        if i in full:
            xs, ids, ok = _jitted(quant, _frozen(m), True)(xs, lp)
        else:
            xs, ids, ok = _jitted(quant, _frozen(m), False)(xs, ids, ok, lp)
    return rmsnorm(xs.sum(axis=1), params["final_norm"], m["norm_eps"])


def head(params, x, quant=EXACT):
    """Next-token logits over the rows of the vocabulary held, in float32,
    of final-RMSNorm rows x (..., d)."""
    q = quant[0]
    return jnp.einsum("...d,vd->...v", q(x), q(f32(params["head_weight"])))


def logits(params, tokens, m, quant=EXACT):
    return head(params, hidden(params, tokens, m, quant), quant)
