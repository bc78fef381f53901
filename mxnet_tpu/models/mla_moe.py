"""Decoder LM with latent attention, a learned sparse-attention indexer and
sigmoid top-k expert routing over a chip's share of the experts, for
incremental decode through :class:`~mxnet_tpu.serving.GenerativePredictor`.

The block (pre-norm, RMSNorm, residual in the compute type; ``h`` is the
normed input of a sublayer; each sublayer gives its branch output ``F(u)``
and :func:`_residual` puts it on the residual path):

- **residual path**.  With ``hc_mult`` 1 one stream, ``x + F(x)``.  With
  ``hc_mult`` n > 1 n streams (the embedding copied into each, summed
  before the head), mixed around every sublayer by a manifold-constrained
  hyper-connection (Hyper-Connections, in DeepSeek's mHC form): from the
  streams ``X`` (n, d) of a token, ``v = vec(X) / rms(vec(X))``,
  ``a = v phi`` (n (n + 2) outputs), ``H_pre = sigmoid(alpha_0 a[:n] + b)``,
  ``H_post = hc_magnitude sigmoid(alpha_1 a[n:2n] + b)``, ``H_res`` the
  Sinkhorn projection (``HC_SINKHORN_ITERS`` turns of row then column
  normalisation, ``hc_eps``) of ``exp(alpha_2 a[2n:] + b)`` as n x n;
  ``u = sum_i H_pre[i] X[i]`` goes through the sublayer and
  ``X[i] <- sum_j H_res[i, j] X[j] + H_post[i] F(u)``.  The maps are float32.
- **latent attention** (DeepSeek-V2/V3's MLA).  ``c_q = RMSNorm(h W_qa)``,
  ``[q_nope | q_rope] = c_q W_qb`` a head, or with ``q_rank`` 0 a full-rank
  query ``h W_q``; with ``qk_norm`` an RMSNorm over each head's query, one
  gain all heads share; ``[c_kv | k_rope] = h W_kva``, ``c_kv =
  RMSNorm(c_kv)``, with ``qk_norm`` an RMSNorm over ``k_rope`` too; interleaved
  rotary on ``q_rope`` and on the one ``k_rope`` all heads share (plain, or
  DeepSeek's YaRN where ``yarn_factor`` is set); ``[k_nope | v] = c_kv
  W_kvb`` a head; ``softmax((q_nope . k_nope + q_rope . k_rope) s)`` with
  ``s = (d_nope + d_rope)^-1/2`` (times YaRN's ``mscale^2``) over the
  *allowed* keys.  With ``attn_sink`` one learned logit a head joins the
  softmax's denominator and carries no value; with ``attn_gate`` each head's
  output is multiplied elementwise by ``sigmoid(h W_g)`` before ``W_o``
  (Gated Attention, position G1).  The cache holds one latent row
  ``[c_kv | k_rope]`` a token a layer.  Prefill runs the expanded form,
  decode the absorbed one (``q' = q_nope W_kvb[k]^T`` scored against
  ``c_kv`` itself, ``P c_kv`` up-projected by ``W_kvb[v]``).
- **indexer** (DeepSeek-V3.2's lightning indexer), every layer, unless
  ``indexer`` is off: then every position attends all earlier ones, and
  decode reads every cached row through :func:`paged_attention` (the
  Pallas kernel ``mx_mla_paged_decode`` on a TPU).
  ``q_I = c_q W_Iq`` (heads x dim), ``k_I = LayerNorm(h W_Ik)``, rotary on
  the first ``index_rope_dim`` of both, ``w = h W_Iw`` scaled by
  ``heads^-1/2 dim^-1/2``; ``I[t, s] = sum_h w[t, h] relu(q_I[t, h] . k_I[s])``
  in float32; position ``t`` may attend the ``index_topk`` causal positions
  of largest ``I`` (all of them while there are no more).  The cache holds
  ``k_I`` beside the latent row, under the same block table.  Where
  ``indexer_types`` names a layer ``shared`` (IndexCache's cross-layer
  reuse), that layer has no indexer, no index keys and no index pool: it
  attends the positions the nearest earlier ``full`` layer chose for the
  same token (in decode that layer's kept ids, in prefill its mask, packed
  32 positions a word).
- **experts** (DeepSeek-V3's ``noaux_tc`` router without groups), layers
  past the leading dense ones.  ``s = sigmoid(h W_r)`` in float32 over all
  ``n_experts``; the ``experts_per_token`` largest of ``s + b`` are chosen
  (``b`` moves the choice only); ``g = route_scale * s / sum(s chosen)``;
  the layer is told which experts it holds (``held_experts``), computes
  ``sum g_e SwiGLU_e(h)`` over the chosen *and held* ones, and adds the
  shared expert.  What the absent experts would have added is left out:
  the partial result goes on, as on one chip of an expert-parallel pool.
  An expert no token of the step chose is skipped (``lax.cond``), so a
  decode step reads the experts it touches.  With ``swiglu_limit`` every
  SwiGLU's gate input is clamped from above and its up input to
  ``[-limit, limit]``.
- **head**: RMSNorm, then an untied head over the rows of the vocabulary
  held here; the embedding has the same rows.  With ``head_fp32`` the
  product is float32.

Entry points are those ``GenerativePredictor`` asks a model module for:
``init_kv_cache``, ``kv_page_bytes``, ``make_prefill_fn``,
``make_decode_fn``, ``decode_counters`` and ``_decode_block_k``;
``make_forward_fn`` is the cache-free one-shot forward the tests hold both
against.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..context import kernel_platform

__all__ = ["LatentMoEConfig", "init_params", "init_kv_cache", "kv_page_bytes",
           "make_prefill_fn", "make_decode_fn", "make_forward_fn",
           "decode_counters", "paged_attention", "blocked_attention"]

# what one decode step counts on the device, summed over its layers and
# returned beside the logits (``profiler.generate_record`` names): with the
# indexer, and without it (every cached row attended)
INDEXED_DECODE_COUNTERS = ("moe_pairs_held", "moe_tokens", "moe_experts_touched",
                           "moe_pairs_at_max_load", "dsa_keys_scanned", "dsa_keys_selected")
DENSE_DECODE_COUNTERS = ("moe_pairs_held", "moe_tokens", "moe_experts_touched",
                         "moe_pairs_at_max_load", "attn_rows_read")
# and where some layers reuse an earlier layer's selection: active slots a
# shared layer
REUSED_DECODE_COUNTER = "dsa_selections_reused"

DECODE_BLOCK_K = 512     # cached rows an online-softmax turn of dense decode
# turns of the hyper-connections' Sinkhorn projection (mHC's count)
HC_SINKHORN_ITERS = 20
# DeepSeek YaRN's correction range: the rotary pairs that turn this many times
# over the original context bound the ramp (``beta_fast``, ``beta_slow``); its
# ``mscale`` and ``mscale_all_dim`` are 1, so cos and sin are not scaled
YARN_BETA_FAST, YARN_BETA_SLOW = 32, 1


@dataclasses.dataclass
class LatentMoEConfig:
    vocab: int = 19360              # rows of the vocabulary held here
    d_model: int = 6144
    n_heads: int = 64
    n_layers: int = 5
    n_dense_layers: int = 1         # leading layers with one dense SwiGLU
    d_ff: int = 12288               # the dense layers' width
    d_expert: int = 2048            # a routed or shared expert's width
    n_experts: int = 256            # the router's width
    experts_per_token: int = 8
    held_experts: tuple = tuple(range(16))   # ids of the experts held here
    route_scale: float = 2.5
    q_rank: int = 2048              # 0: a full-rank query, h W_q
    kv_rank: int = 512
    d_nope: int = 192
    d_rope: int = 64
    d_v: int = 256
    index_heads: int = 32
    index_dim: int = 128
    index_rope_dim: int = 64
    index_topk: int = 2048
    indexer: bool = True            # False: every layer attends all earlier rows
    # RMSNorms over each head's query (one gain) and the shared rotary key
    qk_norm: bool = False
    rope_theta: float = 1e6
    # DeepSeek's YaRN (``rope_scaling`` type ``deepseek_yarn``); factor 0: none
    yarn_factor: float = 0.0
    yarn_original: int = 4096       # original_max_position_embeddings
    norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6
    max_len: int = 202752
    dtype: str = "bfloat16"         # weights as given; cache and products
    # per layer "full" (an indexer of its own) or "shared" (the selection of
    # the nearest earlier full layer); empty: every layer full
    indexer_types: tuple = ()
    # streams of the residual path; more than 1: hyper-connected
    hc_mult: int = 1
    hc_magnitude: float = 2.0       # the scale of H_post
    hc_eps: float = 1e-6            # Sinkhorn's epsilon
    attn_gate: bool = False         # o * sigmoid(h W_g) a head before W_o
    attn_sink: bool = False         # a learned logit a head in the softmax
    swiglu_limit: float = 0.0       # the SwiGLU inputs' clamp; 0: none
    head_fp32: bool = False         # the head's product in float32
    # the module whose programs serve this configuration
    module: str = "mxnet_tpu.models.mla_moe"

    def __post_init__(self):
        self.held_experts = tuple(int(e) for e in self.held_experts)
        self.indexer_types = tuple(str(t) for t in self.indexer_types)
        types = self.indexer_types
        if types and (not self.indexer or len(types) != self.n_layers
                      or types[0] != "full" or set(types) - {"full", "shared"}):
            raise ValueError("indexer_types: one of 'full' or 'shared' a layer, "
                             "the first 'full', and an indexer; got %r" % (types,))
        if (self.attn_gate or self.attn_sink) and not self.indexer:
            raise ValueError("a gated or sink attention decodes through the "
                             "indexed form only")


def param_shapes(config):
    """name -> (shape, kind): ``normal`` matrices, ``ones`` gains, ``zeros``
    offsets, ``bias`` the router's correction bias.  Attention and indexer
    leaves are stacked over all layers, dense FFN leaves over the leading
    dense layers, router and expert leaves over the layers that follow.  A
    full-rank query is ``q_weight`` in the place of ``q_a_*`` and
    ``q_b_weight``; the norms' gains ``q_norm`` and ``k_norm`` are there
    where ``qk_norm`` is on, the indexer's leaves where the indexer is,
    stacked over the layers with an indexer of their own.  Hyper-connections
    add a map a sublayer (``hc_attn_*``, ``hc_ffn_*``: ``proj`` drawn ``hc``,
    ``bias`` drawn ``hc_bias``, ``scale`` the alphas); the gate adds
    ``o_gate_weight``, the sink ``attn_sink``."""
    c = config
    d, L, H = c.d_model, c.n_layers, c.n_heads
    Lf = len(_full_layers(c))
    Ld, Lm, Eh = c.n_dense_layers, c.n_layers - c.n_dense_layers, len(c.held_experts)
    qk = c.d_nope + c.d_rope
    out = {
        "embed_weight": ((c.vocab, d), "normal"),
        "head_weight": ((c.vocab, d), "normal"),
        "final_norm": ((d,), "ones"),
        "attn_norm": ((L, d), "ones"),
        "ffn_norm": ((L, d), "ones"),
        "q_a_weight": ((L, d, c.q_rank), "normal"),
        "q_a_norm": ((L, c.q_rank), "ones"),
        "q_b_weight": ((L, c.q_rank, H, c.d_nope + c.d_rope), "normal"),
        "kv_a_weight": ((L, d, c.kv_rank + c.d_rope), "normal"),
        "kv_a_norm": ((L, c.kv_rank), "ones"),
        "kv_b_weight": ((L, c.kv_rank, H, c.d_nope + c.d_v), "normal"),
        "o_weight": ((L, H, c.d_v, d), "normal"),
        "index_q_weight": ((Lf, c.q_rank, c.index_heads, c.index_dim), "normal"),
        "index_k_weight": ((Lf, d, c.index_dim), "normal"),
        "index_k_norm_gamma": ((Lf, c.index_dim), "ones"),
        "index_k_norm_beta": ((Lf, c.index_dim), "zeros"),
        "index_w_weight": ((Lf, d, c.index_heads), "normal"),
        "dense_gate_weight": ((Ld, d, c.d_ff), "normal"),
        "dense_up_weight": ((Ld, d, c.d_ff), "normal"),
        "dense_down_weight": ((Ld, c.d_ff, d), "normal"),
        "router_weight": ((Lm, d, c.n_experts), "normal"),
        "router_bias": ((Lm, c.n_experts), "bias"),
        "expert_gate_weight": ((Lm, Eh, d, c.d_expert), "normal"),
        "expert_up_weight": ((Lm, Eh, d, c.d_expert), "normal"),
        "expert_down_weight": ((Lm, Eh, c.d_expert, d), "normal"),
        "shared_gate_weight": ((Lm, d, c.d_expert), "normal"),
        "shared_up_weight": ((Lm, d, c.d_expert), "normal"),
        "shared_down_weight": ((Lm, c.d_expert, d), "normal"),
    }
    if not c.q_rank:
        for k in ("q_a_weight", "q_a_norm", "q_b_weight"):
            del out[k]
        out["q_weight"] = ((L, d, H, qk), "normal")
    if not c.indexer:
        for k in [k for k in out if k.startswith("index_")]:
            del out[k]
    if c.qk_norm:
        out["q_norm"] = ((L, qk), "ones")
        out["k_norm"] = ((L, c.d_rope), "ones")
    n = c.hc_mult
    if n > 1:
        for half in ("attn", "ffn"):
            out["hc_%s_proj" % half] = ((L, n * d, n * (n + 2)), "hc")
            out["hc_%s_bias" % half] = ((L, n * (n + 2)), "hc_bias")
            out["hc_%s_scale" % half] = ((L, 3), "ones")
    if c.attn_gate:
        # the heads' lanes side by side: a (d, H, d_v) matrix is no bitcast
        # of (d, H d_v) under the TPU's tiling, and its product would copy
        # it to another layout every step
        out["o_gate_weight"] = ((L, d, H * c.d_v), "normal")
    if c.attn_sink:
        out["attn_sink"] = ((L, H), "sink")
    return out


def init_params(config, seed=0, scale=0.02, bias_scale=0.01):
    """Seeded float32 parameters on the host (tests and examples; the
    benchmark makes its own on the device): matrices normal(0, ``scale``),
    a hyper-connection's projection normal(0, ``scale / sqrt(hc_mult)``)
    (its input is ``hc_mult`` times wider), its biases and the sinks
    normal(0, 1), the router's bias normal(0, ``bias_scale``)."""
    rng = np.random.RandomState(seed)
    std = {"bias": bias_scale, "hc": scale / np.sqrt(config.hc_mult),
           "hc_bias": 1.0, "sink": 1.0}
    out = {}
    for name, (shape, kind) in sorted(param_shapes(config).items()):
        if kind == "ones":
            out[name] = np.ones(shape, np.float32)
        elif kind == "zeros":
            out[name] = np.zeros(shape, np.float32)
        else:
            out[name] = rng.normal(0.0, std.get(kind, scale), shape).astype(np.float32)
    return {k: jnp.asarray(v) for k, v in out.items()}


# -- the cache ---------------------------------------------------------------
def _latent_width(config):
    """Lanes of a cached latent row: ``kv_rank + d_rope`` rounded up to whole
    128-lane tiles (576 -> 640), the tail zero.  A row that is not whole
    tiles makes the TPU lay the pool out with the page axis minor-most (less
    padding that way), and every gather and scatter of rows then pays two
    transposing copies of the layer's pool a step."""
    return -(-(config.kv_rank + config.d_rope) // 128) * 128


def _full_layers(config):
    """The layers with an indexer of their own, in order."""
    c = config
    if not c.indexer:
        return ()
    types = c.indexer_types or ("full",) * c.n_layers
    return tuple(i for i, t in enumerate(types) if t == "full")


def _index_rank(config, i):
    """Layer ``i``'s place among :func:`_full_layers` (its index leaves and
    index pool), None where it has no indexer of its own."""
    full = _full_layers(config)
    return full.index(i) if i in full else None


def init_kv_cache(config, num_pages, page_size, dtype=None):
    """Zeroed page pool under one block table: ``latent[l]`` (pages + 1,
    page, :func:`_latent_width`) rows ``[c_kv | k_rope | 0]`` a layer and
    ``index[f]`` (pages + 1, page, index_dim) rows ``k_I`` a layer with an
    indexer of its own (:func:`_full_layers`); without the indexer the
    latent rows alone.  A layer's
    arrays are its own, so that a step writes its row into them in place and
    gathers from them without slicing a pool of all layers first.  Page 0 is
    the scratch page, as in the transformer's pool."""
    c = config
    cdt = jnp.dtype(dtype if dtype is not None else c.dtype)
    lead = (int(num_pages) + 1, int(page_size))
    pools = {"latent": [jnp.zeros(lead + (_latent_width(c),), cdt)
                        for _ in range(c.n_layers)]}
    if c.indexer:
        pools["index"] = [jnp.zeros(lead + (c.index_dim,), cdt)
                          for _ in _full_layers(c)]
    return pools


def kv_page_bytes(config, page_size):
    """Bytes one page holds over all layers and arrays."""
    c = config
    return (int(page_size) * (c.n_layers * _latent_width(c)
                              + len(_full_layers(c)) * c.index_dim)
            * jnp.dtype(c.dtype).itemsize)


def decode_counters(config):
    """Names of what the decode program counts for ``config``."""
    if not config.indexer:
        return DENSE_DECODE_COUNTERS
    if len(_full_layers(config)) < config.n_layers:
        return INDEXED_DECODE_COUNTERS + (REUSED_DECODE_COUNTER,)
    return INDEXED_DECODE_COUNTERS


def _decode_block_k(config, slots, max_ctx):
    """Cached rows an online-softmax turn of dense decode, as the predictor
    asks (the kernel and the blocked form cut it to whole pages)."""
    return min(DECODE_BLOCK_K, int(max_ctx))


# -- pieces ------------------------------------------------------------------
def _rmsnorm(x, gamma, eps):
    x = x.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y * gamma.astype(jnp.float32)


def _layernorm(x, gamma, beta, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * gamma.astype(jnp.float32) \
        + beta.astype(jnp.float32)


def _rope(x, positions, theta, freq=None):
    """Interleaved rotary over the last axis of ``x`` (..., T, [heads,] n):
    the pairs ``(x[2i], x[2i+1])`` turn by ``positions * freq[i]``, by default
    ``theta^(-2i/n)``.  ``positions`` (T,) lines up with the axis before the
    optional heads."""
    n = x.shape[-1]
    if freq is None:
        freq = jnp.exp(jnp.arange(0, n, 2, dtype=jnp.float32) * (-np.log(theta) / n))
    ang = positions.astype(jnp.float32)[:, None] * freq          # (T, n/2)
    if x.ndim == ang.ndim + 1:
        ang = ang[:, None, :]                                    # heads axis
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (n // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def _yarn_mscale(factor):
    """YaRN's attention factor ``0.1 ln(factor) + 1`` (1 unscaled)."""
    return 0.1 * np.log(factor) + 1.0 if factor > 1 else 1.0


def _yarn_freq(c, n):
    """DeepSeek's YaRN frequencies of the ``n / 2`` rotary pairs, float32:
    pairs up to the correction range's low end keep ``theta^(-2i/n)``, pairs
    from its high end on turn ``yarn_factor`` times slower, and a linear
    ramp blends the two between.  The range's ends are the pairs that turn
    ``YARN_BETA_FAST`` and ``YARN_BETA_SLOW`` times over ``yarn_original``
    positions."""
    theta, factor = float(c.rope_theta), float(c.yarn_factor)

    def pair(turns):
        return n * np.log(c.yarn_original / (turns * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(pair(YARN_BETA_FAST)), 0)
    high = min(np.ceil(pair(YARN_BETA_SLOW)), n - 1)
    high = high + 0.001 if high == low else high
    extra = theta ** (-np.arange(0, n, 2, dtype=np.float64) / n)
    ramp = np.clip((np.arange(n // 2) - low) / (high - low), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def _rotary(x, positions, c):
    """:func:`_rope` with the configuration's frequencies: plain, or YaRN's
    where ``yarn_factor`` is set."""
    if not getattr(c, "yarn_factor", 0.0):
        return _rope(x, positions, c.rope_theta)
    return _rope(x, positions, c.rope_theta, jnp.asarray(_yarn_freq(c, x.shape[-1])))


def _softmax_scale(c):
    """``(d_nope + d_rope)^-1/2``, times YaRN's attention factor squared
    where YaRN is on."""
    return (c.d_nope + c.d_rope) ** -0.5 * _yarn_mscale(getattr(c, "yarn_factor", 0.0)) ** 2


def _dot(a, b, spec, cdt):
    """A product in the compute type, accumulated in float32."""
    return jnp.einsum(spec, a.astype(cdt), b.astype(cdt),
                      preferred_element_type=jnp.float32)


def _swiglu(x, gate, up, down, cdt, limit=0.0):
    """``(silu(x W_g) * (x W_u)) W_d``; with ``limit`` the gate input is
    clamped from above and the up input to ``[-limit, limit]``."""
    g = _dot(x, gate, "td,df->tf", cdt)
    u = _dot(x, up, "td,df->tf", cdt)
    if limit:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return _dot((jax.nn.silu(g) * u).astype(cdt), down, "tf,fd->td", cdt)


# the leaves stacked over all layers, those a configuration has (the
# indexer's over the layers with one of their own)
_LAYER_LEAVES = ("attn_norm", "ffn_norm", "q_a_weight", "q_a_norm", "q_b_weight",
                 "kv_a_weight", "kv_a_norm", "kv_b_weight", "o_weight",
                 "index_q_weight", "index_k_weight", "index_k_norm_gamma",
                 "index_k_norm_beta", "index_w_weight", "q_weight", "q_norm", "k_norm",
                 "hc_attn_proj", "hc_attn_bias", "hc_attn_scale", "hc_ffn_proj",
                 "hc_ffn_bias", "hc_ffn_scale", "attn_sink")


def _layer(params, i, config):
    """Layer ``i``'s leaves: attention and indexer from their stacks (none
    of the indexer's where the layer reuses a selection), the FFN's from
    the dense or the expert stacks."""
    c = config
    f = _index_rank(c, i)
    lp = {}
    for k in _LAYER_LEAVES:
        if k not in params:
            continue
        if not k.startswith("index_"):
            lp[k] = params[k][i]
        elif f is not None:
            lp[k] = params[k][f]
    if "o_gate_weight" in params:
        # the stack whole, with the layer's index: sliced where it is used
        # (inside prefill's row blocks), never copied out to be handed in
        lp["o_gate_weight"] = (i, params["o_gate_weight"])
    if i < c.n_dense_layers:
        group, j = ("dense_gate_weight", "dense_up_weight", "dense_down_weight"), i
    else:
        group = ("router_weight", "router_bias", "shared_gate_weight",
                 "shared_up_weight", "shared_down_weight")
        j = i - c.n_dense_layers
        # the held experts' stacks go whole, with the layer's index: an
        # expert's matrices are sliced where they are used, inside the branch
        # that may skip them, and never copied out to be handed to it
        lp["experts"] = (j, params["expert_gate_weight"],
                         params["expert_up_weight"], params["expert_down_weight"])
    lp.update({k: params[k][j] for k in group})
    return lp


def _latent_project(h, positions, lp, c, cdt, kv_scale=None):
    """The low-rank projections of rows ``h`` (T, d) at ``positions`` (T,):
    ``c_q`` (T, q_rank) and the latent row as it is cached
    (T, :func:`_latent_width`), both in the compute type.  A full-rank
    query's ``c_q`` is ``h`` itself (:func:`_queries` projects it).
    ``kv_scale`` multiplies the normed ``c_kv`` (not the rotary key) before
    the cast; ``k_norm``, where the layer has it, norms the rotary key
    before it is turned."""
    with jax.named_scope("mx.gen.latent_proj"):
        if "q_a_weight" in lp:
            c_q = _rmsnorm(_dot(h, lp["q_a_weight"], "td,dr->tr", cdt),
                           lp["q_a_norm"], c.norm_eps).astype(cdt)
        else:
            c_q = h
        kv = _dot(h, lp["kv_a_weight"], "td,dr->tr", cdt)
        c_kv = _rmsnorm(kv[:, :c.kv_rank], lp["kv_a_norm"], c.norm_eps)
        if kv_scale is not None:
            c_kv = c_kv * kv_scale
        k_r = kv[:, c.kv_rank:]
        if "k_norm" in lp:
            k_r = _rmsnorm(k_r, lp["k_norm"], c.norm_eps)
        k_rope = _rotary(k_r, positions, c)
        pad = jnp.zeros((h.shape[0], _latent_width(c) - c.kv_rank - c.d_rope),
                        jnp.float32)
        latent = jnp.concatenate([c_kv, k_rope, pad], axis=-1).astype(cdt)
    return c_q, latent


def _index_project(h, c_q, positions, lp, c, cdt):
    """The indexer's projections of rows ``h`` (T, d) with their ``c_q``:
    ``q_I`` (T, heads, dim) and ``k_I`` (T, dim) in the compute type, and the
    head weights ``w`` (T, heads) in float32."""
    with jax.named_scope("mx.gen.index"):
        r = c.index_rope_dim
        q_i = _dot(c_q, lp["index_q_weight"], "tr,rhe->the", cdt)
        q_i = jnp.concatenate([_rotary(q_i[..., :r], positions, c),
                               q_i[..., r:]], axis=-1).astype(cdt)
        k_i = _layernorm(_dot(h, lp["index_k_weight"], "td,de->te", cdt),
                         lp["index_k_norm_gamma"], lp["index_k_norm_beta"],
                         c.index_norm_eps)
        k_i = jnp.concatenate([_rotary(k_i[:, :r], positions, c),
                               k_i[:, r:]], axis=-1).astype(cdt)
        w = _dot(h, lp["index_w_weight"], "td,dh->th", cdt) \
            * (c.index_heads ** -0.5 * c.index_dim ** -0.5)
    return q_i, k_i, w


def _project(h, positions, lp, c, cdt):
    """:func:`_latent_project` and :func:`_index_project` of rows ``h``:
    ``c_q``, the latent row, ``q_I``, ``k_I`` and ``w``."""
    c_q, latent = _latent_project(h, positions, lp, c, cdt)
    return (c_q, latent) + _index_project(h, c_q, positions, lp, c, cdt)


def _queries(c_q, positions, lp, c, cdt, scale=None):
    """(T, H, d_nope) and rotated (T, H, d_rope) queries from ``c_q`` (the
    rows themselves where the query is full-rank), both times ``scale`` where
    one is given, each head's normed where the layer has ``q_norm``."""
    with jax.named_scope("mx.gen.latent_proj"):
        w = lp["q_b_weight"] if "q_b_weight" in lp else lp["q_weight"]
        q = _dot(c_q, w, "tr,rhe->the", cdt)
        if scale is not None:
            q = q * scale
        if "q_norm" in lp:
            q = _rmsnorm(q, lp["q_norm"], c.norm_eps)
        q_rope = _rotary(q[..., c.d_nope:], positions, c)
        return q[..., :c.d_nope].astype(cdt), q_rope.astype(cdt)


def _k_b(lp, c):
    """The key half of the up-projection, (kv_rank, H, d_nope): a leaf of
    its own where the layer keeps the halves apart, else the head of
    ``kv_b_weight``."""
    return lp["k_b_weight"] if "k_b_weight" in lp else lp["kv_b_weight"][..., :c.d_nope]


def _v_b(lp, c):
    """The value half, (kv_rank, H, d_v), likewise."""
    return lp["v_b_weight"] if "v_b_weight" in lp else lp["kv_b_weight"][..., c.d_nope:]


def _output(o, lp, cdt):
    """Heads' values (T, H, d_v) through the output projection: (T, d)."""
    with jax.named_scope("mx.gen.latent_proj"):
        return _dot(o, lp["o_weight"], "the,hed->td", cdt)


def _index_scores(q_i, w, k_i):
    """``I`` (T, K) float32 of queries ``q_i`` (T, heads, dim) with head
    weights ``w`` (T, heads) against keys ``k_i`` (K, dim) or, a query its
    own keys, (T, K, dim)."""
    spec = "the,tke->thk" if k_i.ndim == 3 else "the,ke->thk"
    s = jnp.einsum(spec, q_i, k_i, preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w[:, :, None], axis=1)


def _largest_k(scores, k):
    """(T, K) bool: per row of ``scores`` (T, K) float32, its ``k`` largest
    entries, equal ones taken from the lower index first, which is the set
    ``lax.top_k`` returns, found without a sort.  The ``k``-th largest value
    comes from a bisection over the 32 bits of an order-preserving unsigned
    code; entries above it are in, and of those equal to it the first ones
    that still fit.  A row of fewer than ``k`` entries above -inf takes those
    and fills up with -inf ones, which the caller's own mask drops."""
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    code = jnp.where(bits < 0, ~bits, bits ^ jnp.int32(-2 ** 31))
    code = lax.bitcast_convert_type(code, jnp.uint32)

    def step(b, key):
        cand = key | (jnp.uint32(1) << (jnp.uint32(31) - b.astype(jnp.uint32)))
        enough = jnp.sum(code >= cand[:, None], axis=-1) >= k
        return jnp.where(enough, cand, key)

    key = lax.fori_loop(0, 32, step, jnp.zeros(scores.shape[:1], jnp.uint32))
    above, equal = code > key[:, None], code == key[:, None]
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal, axis=-1) <= room))


def _route(h, lp, c, active):
    """The router over all experts, in float32: per token the chosen ids
    (T, k) and their gates (T, k); rows where ``active`` is false choose
    nothing (id -1, gate 0)."""
    with jax.named_scope("mx.lm.moe.route"):
        logits = jnp.einsum("td,de->te", h, lp["router_weight"].astype(h.dtype),
                            preferred_element_type=jnp.float32)
        s = jax.nn.sigmoid(logits)
        _top, ids = lax.top_k(s + lp["router_bias"].astype(jnp.float32),
                              c.experts_per_token)
        chosen = jnp.take_along_axis(s, ids, axis=-1)
        gates = c.route_scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        ids = jnp.where(active[:, None], ids, -1)
        gates = jnp.where(active[:, None], gates, 0.0)
    return ids, gates


def _held_experts(h, ids, gates, experts, c, cdt, y):
    """``y`` plus what the held experts give rows ``h`` (T, d) under the
    router's choice ``ids`` / ``gates`` (T, k), and the pairs each held
    expert took (held,).  ``experts`` is the layer's index with the three
    stacks (layers, held, ...), or None with one layer's own (held, ...); an
    expert no row chose is skipped."""
    loads = []
    layer, *stacks = experts
    with jax.named_scope("mx.lm.moe.experts"):
        for j, e in enumerate(c.held_experts):
            hit = ids == e                                       # (T, k)
            gate = jnp.sum(jnp.where(hit, gates, 0.0), axis=-1)  # (T,)
            loads.append(jnp.sum(hit))

            def run(j=j, gate=gate):
                at = j if layer is None else (layer, j)
                out = _swiglu(h, *(w[at] for w in stacks), cdt,
                              getattr(c, "swiglu_limit", 0.0))
                return out * gate[:, None]

            y = y + lax.cond(loads[-1] > 0, run, lambda: jnp.zeros_like(y))
    return y, jnp.stack(loads)


def _moe_counts(loads, active, c):
    """What an expert layer counted: pairs on held experts, rows routed, held
    experts touched, and the pairs it would hold were every held expert as
    full as the fullest."""
    return {"moe_pairs_held": jnp.sum(loads),
            "moe_tokens": jnp.sum(active),
            "moe_experts_touched": jnp.sum(loads > 0),
            "moe_pairs_at_max_load": jnp.max(loads) * len(c.held_experts)}


def _moe(h, lp, c, cdt, active):
    """The expert layer's output for normed rows ``h`` (T, d), the shared
    expert's with the held experts' on top, and what it counted."""
    ids, gates = _route(h, lp, c, active)
    with jax.named_scope("mx.lm.moe.shared"):
        y = _swiglu(h, lp["shared_gate_weight"], lp["shared_up_weight"],
                    lp["shared_down_weight"], cdt, c.swiglu_limit)
    y, loads = _held_experts(h, ids, gates, lp["experts"], c, cdt, y)
    return y, _moe_counts(loads, active, c)


def _ffn(u, lp, c, cdt, active):
    """The FFN sublayer's branch on rows ``u`` (T, d): its output and what
    it counted."""
    h = _rmsnorm(u, lp["ffn_norm"], c.norm_eps).astype(cdt)
    if "router_weight" in lp:
        y, counts = _moe(h, lp, c, cdt, active)
    else:
        with jax.named_scope("mx.lm.ffn"):
            y = _swiglu(h, lp["dense_gate_weight"], lp["dense_up_weight"],
                        lp["dense_down_weight"], cdt, c.swiglu_limit)
        counts = {}
    return y, counts


# -- the residual path -------------------------------------------------------
def _streams(x, c):
    """Embedded rows (T, d) as the residual path's carry: themselves, or
    copied into each of ``hc_mult`` streams (T, n, d)."""
    if c.hc_mult == 1:
        return x
    with jax.named_scope("mx.lm.hc"):
        return jnp.broadcast_to(x[:, None], (x.shape[0], c.hc_mult, x.shape[1]))


def _merged(x, c):
    """The carry as the head takes it: the streams' sum in float32."""
    if c.hc_mult == 1:
        return x
    with jax.named_scope("mx.lm.hc"):
        return jnp.sum(x.astype(jnp.float32), axis=1)


def _hc_in(x, lp, half, c, cdt):
    """What sublayer ``half`` reads of the carry ``x`` and the maps that put
    its output back: ``(x, None)`` on a plain residual; with
    hyper-connections (``hc_<half>_*`` leaves) ``u = sum_i H_pre[i] X[i]``
    (T, d) and ``(H_post (T, n), H_res (T, n, n))``, float32, from
    ``v = vec(X) / rms(vec(X))`` projected once (``v phi`` is ``vec(X) phi``
    over the rms, so the product takes the carry as it is)."""
    if "hc_%s_proj" % half not in lp:
        return x, None
    T, n, d = x.shape
    with jax.named_scope("mx.lm.hc"):
        flat = x.reshape(T, n * d)
        ms = jnp.mean(jnp.square(flat.astype(jnp.float32)), axis=-1, keepdims=True)
        a = _dot(flat, lp["hc_%s_proj" % half], "tk,km->tm", cdt) \
            * lax.rsqrt(ms + c.norm_eps)
        alpha = lp["hc_%s_scale" % half].astype(jnp.float32)
        b = lp["hc_%s_bias" % half].astype(jnp.float32)
        pre = jax.nn.sigmoid(alpha[0] * a[:, :n] + b[:n])
        post = c.hc_magnitude * jax.nn.sigmoid(alpha[1] * a[:, n:2 * n] + b[n:2 * n])
        res = jnp.exp(alpha[2] * a[:, 2 * n:] + b[2 * n:]).reshape(T, n, n)
        for _ in range(HC_SINKHORN_ITERS):
            res = res / (jnp.sum(res, axis=-1, keepdims=True) + c.hc_eps)
            res = res / (jnp.sum(res, axis=-2, keepdims=True) + c.hc_eps)
        u = jnp.sum(pre[:, :, None] * x.astype(jnp.float32), axis=1).astype(cdt)
    return u, (post, res)


def _hc_out(x, y, maps, cdt):
    """The carry after a sublayer whose branch gave ``y`` (T, d): ``x + y``
    on a plain residual, else ``X[i] <- sum_j H_res[i, j] X[j] +
    H_post[i] y``."""
    if maps is None:
        return x + y.astype(cdt)
    post, res = maps
    with jax.named_scope("mx.lm.hc"):
        mixed = jnp.sum(res[..., None] * x.astype(jnp.float32)[:, None], axis=2)
        return (mixed + post[:, :, None] * y.astype(cdt)[:, None, :]).astype(cdt)


def _residual(x, branch, lp, half, c, cdt):
    """Sublayer ``half`` on the residual path: ``branch(u)`` gives its output
    and what it counted; returns the new carry and the count."""
    u, maps = _hc_in(x, lp, half, c, cdt)
    y, extra = branch(u)
    return _hc_out(x, y, maps, cdt), extra


def _exact_dot(x, w, spec):
    """Float32 ``x`` times ``w`` to float32 rounding: where ``w`` is narrower,
    ``x`` as the sum of three parts of ``w``'s type, each product with ``w``
    exact, accumulated in float32 in one product."""
    if w.dtype == jnp.float32:
        return jnp.einsum(spec, x, w, preferred_element_type=jnp.float32)
    parts, rest = [], x
    for _ in range(3):
        parts.append(rest.astype(w.dtype))
        rest = rest - parts[-1].astype(jnp.float32)
    y = jnp.einsum(spec, jnp.concatenate(parts), w, preferred_element_type=jnp.float32)
    return sum(jnp.split(y, 3))


def _head(x, params, c, cdt):
    if getattr(c, "head_fp32", False):
        return _exact_dot(_rmsnorm(x, params["final_norm"], c.norm_eps),
                          params["head_weight"], "td,vd->tv")
    h = _rmsnorm(x, params["final_norm"], c.norm_eps).astype(cdt)
    return _dot(h, params["head_weight"], "td,vd->tv", cdt)


def _row_blocks(fn, rows, block, *args):
    """``fn`` over blocks of ``block`` rows of every array in ``args`` (each
    (rows, ...)), one block at a time, results concatenated."""
    nb = rows // block
    split = [a.reshape((nb, block) + a.shape[1:]) for a in args]
    out = lax.map(lambda xs: fn(*xs), tuple(split))
    return jax.tree.map(lambda o: o.reshape((rows,) + o.shape[2:]), out)


KEY_CHUNKS = 8       # key chunks a query block may skip when they lie ahead
KEY_SPAN = 4096      # keys a chunk holds at most: longer prompts take more chunks


def _pack(mask):
    """(rows, K) bool as (rows, ceil(K / 32)) uint32, position ``32 w + j``
    in bit ``j`` of word ``w``."""
    rows, k = mask.shape
    bits = jnp.pad(mask, ((0, 0), (0, -k % 32))).reshape(rows, -1, 32)
    return jnp.sum(bits.astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def _unpack(words, k):
    """:func:`_pack`'s inverse: (rows, k) bool."""
    bits = (words[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(words.shape[0], -1)[:, :k].astype(bool)


def _gate(h, lp, c, cdt):
    """``sigmoid(h W_g)`` (T, H, d_v) float32, the elementwise output gate
    of rows ``h`` (T, d); ``W_g`` is (d, H d_v)."""
    layer, stack = lp["o_gate_weight"]
    with jax.named_scope("mx.gen.latent_proj"):
        g = _dot(h, stack[layer], "td,df->tf", cdt)
        return jax.nn.sigmoid(g).reshape(h.shape[0], c.n_heads, c.d_v)


def _expanded_attention(c_q, positions, length, latent, lp, c, cdt, index=None,
                        q_scale=None, h=None, keep=False, out_dtype=jnp.float32):
    """Causal attention of a whole sequence in the expanded form: keys and
    values of every head are made from the latent rows once; the queries go
    through in row blocks and the keys in ``KEY_CHUNKS`` chunks (online
    softmax), so that nothing of (rows x keys x heads) outlives its chunk.
    A chunk of keys that lies wholly ahead of a block's rows is skipped, and
    so is a block of rows wholly past ``length`` (the padded tail of a
    prompt).  With ``index`` = (``q_I``, ``w``, ``k_I``) a row attends the
    ``index_topk`` causal keys of largest index score, with ``index`` an
    earlier layer's selection (T, ceil(T / 32)) as :func:`_pack` packs it
    those keys, without it every causal key; ``q_scale`` goes to
    :func:`_queries`.  A layer with ``attn_sink`` starts each head's softmax
    from its sink; one with ``o_gate_weight`` gates the heads' values by
    :func:`_gate` of its normed rows ``h``.  Returns (T, d) in ``out_dtype``,
    through the output projection, and with ``keep`` the selection, packed,
    beside it."""
    T = c_q.shape[0]
    with jax.named_scope("mx.gen.attn"):
        c_kv = latent[:, :c.kv_rank]
        k_nope = _dot(c_kv, _k_b(lp, c), "sr,rhe->she", cdt).astype(cdt)
        v = _dot(c_kv, _v_b(lp, c), "sr,rhe->she", cdt).astype(cdt)
        k_rope = latent[:, c.kv_rank:c.kv_rank + c.d_rope]
    scale = _softmax_scale(c)
    key_pos = jnp.arange(T)
    chunks = KEY_CHUNKS if T % KEY_CHUNKS == 0 else 1
    while T // chunks > KEY_SPAN and T % (2 * chunks) == 0:
        chunks *= 2
    span = T // chunks
    cuts = [slice(j * span, (j + 1) * span) for j in range(chunks)]
    rows = min(T, 256)
    while T % rows:
        rows -= 1
    H = c.n_heads
    carried = index is not None and not isinstance(index, tuple)
    gated = "o_gate_weight" in lp

    def select(q_i, w, pos, last):
        with jax.named_scope("mx.gen.index"):
            causal = key_pos[None, :] <= pos[:, None]
            parts = [lax.cond(cut.start <= last,
                              lambda cut=cut: _index_scores(q_i, w, index[2][cut]),
                              lambda: jnp.zeros((rows, span), jnp.float32))
                     for cut in cuts]
            scores = jnp.where(causal, jnp.concatenate(parts, axis=-1), -jnp.inf)
            return causal & _largest_k(scores, c.index_topk)

    def attend(c_q, selectors, pos, h_rows):
        last = pos[-1]
        if carried:
            allowed = _unpack(selectors[0], T)
        elif index is not None:
            allowed = select(*selectors, pos, last)
        else:
            allowed = key_pos[None, :] <= pos[:, None]
        q_nope, q_rope = _queries(c_q, pos, lp, c, cdt, q_scale)

        def chunk(cut, top, norm, acc):
            s = (jnp.einsum("the,she->hts", q_nope, k_nope[cut],
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("the,se->hts", q_rope, k_rope[cut],
                              preferred_element_type=jnp.float32)) * scale
            s = jnp.where(allowed[None, :, cut], s, -jnp.inf)
            new = jnp.maximum(top, jnp.max(s, axis=-1))
            # a row that has met no allowed key yet keeps -inf: shift by 0
            shift = jnp.where(new == -jnp.inf, 0.0, new)
            keep_ = jnp.exp(top - shift)
            p = jnp.exp(s - shift[..., None])
            pv = jnp.einsum("hts,she->hte", p.astype(cdt), v[cut],
                            preferred_element_type=jnp.float32)
            return (new, norm * keep_ + jnp.sum(p, axis=-1),
                    acc * keep_[..., None] + pv)

        gate = _gate(h_rows, lp, c, cdt) if gated else None
        with jax.named_scope("mx.gen.attn"):
            if "attn_sink" in lp:
                # the sink is a key of logit ``sink`` and no value
                sink = jnp.broadcast_to(lp["attn_sink"].astype(jnp.float32)[:, None],
                                        (H, rows))
                state = (sink, jnp.ones((H, rows), jnp.float32),
                         jnp.zeros((H, rows, c.d_v), jnp.float32))
            else:
                state = (jnp.full((H, rows), -jnp.inf, jnp.float32),
                         jnp.zeros((H, rows), jnp.float32),
                         jnp.zeros((H, rows, c.d_v), jnp.float32))
            for cut in cuts:
                state = lax.cond(cut.start <= last,
                                 lambda st, cut=cut: chunk(cut, *st),
                                 lambda st: st, state)
            _top, norm, acc = state
            o = (acc / norm[..., None]).transpose(1, 0, 2)
            if gate is not None:
                o = o * gate
        out = _output(o, lp, cdt).astype(out_dtype)
        return (out, _pack(allowed)) if keep else out

    def block(c_q, *rest):
        *selectors, pos = rest         # the indexer's rows, where there is one
        h_rows = selectors.pop() if gated else None

        def skipped():
            out = jnp.zeros((rows, c.d_model), out_dtype)
            return (out, jnp.zeros((rows, -(-T // 32)), jnp.uint32)) if keep else out

        return lax.cond(pos[0] < length, lambda: attend(c_q, selectors, pos, h_rows),
                        skipped)

    selectors = () if index is None else (index,) if carried else index[:2]
    return _row_blocks(block, T, rows, c_q, *selectors, *((h,) if gated else ()),
                       positions)


def blocked_attention(q, pool, block_tables, lengths, d_value, scale, block_k):
    """The absorbed decode attention in ``jax.numpy``: queries ``q``
    (S, H, W) against each slot's rows of ``pool`` (pages + 1, page, W),
    ``block_k`` rows (whole pages) a turn through the block table with an
    online softmax; turns past the longest length are not made.  Returns
    (S, H, d_value) float32: softmax(scale q . rows) rows[:, :d_value] over
    rows ``< lengths[b]``, zeros for a slot of length 0."""
    S, H, W = q.shape
    page = pool.shape[1]
    per_turn = max(1, int(block_k) // page)
    span = per_turn * page
    pad = -block_tables.shape[1] % per_turn
    table = jnp.pad(block_tables, ((0, 0), (0, pad)))
    turns = (jnp.max(lengths) + span - 1) // span

    def turn(j, state):
        top, norm, acc = state
        ids = lax.dynamic_slice_in_dim(table, j * per_turn, per_turn, axis=1)
        rows = pool[ids].reshape(S, span, W)
        s = jnp.einsum("shw,skw->shk", q, rows,
                       preferred_element_type=jnp.float32) * scale
        valid = (j * span + jnp.arange(span))[None, :] < lengths[:, None]
        s = jnp.where(valid[:, None, :], s, -jnp.inf)
        new = jnp.maximum(top, jnp.max(s, axis=-1))
        shift = jnp.where(new == -jnp.inf, 0.0, new)     # no valid row met yet
        keep = jnp.exp(top - shift)
        p = jnp.exp(s - shift[..., None])
        pv = jnp.einsum("shk,skv->shv", p.astype(rows.dtype), rows[..., :d_value],
                        preferred_element_type=jnp.float32)
        return new, norm * keep + jnp.sum(p, axis=-1), acc * keep[..., None] + pv

    _top, norm, acc = lax.fori_loop(
        0, turns, turn, (jnp.full((S, H), -jnp.inf, jnp.float32),
                         jnp.zeros((S, H), jnp.float32),
                         jnp.zeros((S, H, d_value), jnp.float32)))
    return acc / jnp.maximum(norm, 1e-30)[..., None]


def paged_attention(pool, q_nope, q_rope, lengths, block_tables, lp, c, cdt, block_k):
    """One layer's dense decode attention in the absorbed form, every cached
    row of each slot up to ``lengths[b]``: the query ``[q_nope W_kb^T | q_rope
    | 0]`` of every head (S, H, :func:`_latent_width`) against the rows of
    ``pool`` where they lie, ``block_k`` rows a turn with an online softmax
    (scale :func:`_softmax_scale`), then ``P c_kv`` up-projected by ``W_vb`` and through the output
    projection: (S, d) float32.  On a TPU the Pallas kernel
    ``kernels/mla_paged_decode.py`` reads the pages in place; elsewhere
    :func:`blocked_attention` gathers a block of pages a turn."""
    S = q_nope.shape[0]
    scale = _softmax_scale(c)
    with jax.named_scope("mx.gen.attn"):
        q_lat = _dot(q_nope, _k_b(lp, c), "she,rhe->shr", cdt).astype(cdt)
        pad = jnp.zeros((S, c.n_heads, _latent_width(c) - c.kv_rank - c.d_rope), cdt)
        q = jnp.concatenate([q_lat, q_rope, pad], axis=-1)       # (S, H, W)
        if kernel_platform() == "tpu":
            from ..kernels.mla_paged_decode import mla_paged_decode_attention

            o_lat = mla_paged_decode_attention(q, pool, block_tables, lengths,
                                               d_value=c.kv_rank, scale=scale,
                                               block_k=block_k)
        else:
            o_lat = blocked_attention(q, pool, block_tables, lengths, c.kv_rank,
                                      scale, block_k).astype(cdt)
        o = _dot(o_lat, _v_b(lp, c), "shr,rhe->she", cdt)
    return _output(o, lp, cdt)


# -- programs ----------------------------------------------------------------
def _sequence_layers(params, x, config, on_layer, length=None):
    """All layers over one whole sequence ``x`` (T, d) at positions
    0..T-1, expanded attention; ``on_layer(i, latent, k_i)`` sees what a
    cache would hold (``k_i`` None where the layer has no indexer of its
    own).  Rows from ``length`` on (a prompt's padded tail) are carried
    along, not computed.  Returns the carry: (T, d), or (T, n, d) with
    ``hc_mult`` n streams, whose maps go a block of rows at a time."""
    c = config
    cdt = jnp.dtype(c.dtype)
    T = x.shape[0]
    positions = jnp.arange(T)
    length = T if length is None else length
    real = positions < length
    ffn_rows = min(T, 1024)
    while T % ffn_rows:
        ffn_rows -= 1
    full = _full_layers(c)

    def ffn(lp, xb, real):
        return lax.cond(real[0], lambda: _residual(
            xb, lambda u: _ffn(u, lp, c, cdt, real), lp, "ffn", c, cdt)[0], lambda: xb)

    def hc_in(lp, xb):
        return _hc_in(xb, lp, "attn", c, cdt)

    def hc_out(xb, ob, *maps):
        return _hc_out(xb, ob, maps, cdt)

    x = _streams(x, c)
    selection = None           # the latest full layer's, where a shared one follows
    for i in range(c.n_layers):
        lp = _layer(params, i, c)
        if c.hc_mult == 1:
            u, maps = x, None
        else:
            u, maps = _row_blocks(functools.partial(hc_in, lp), T, ffn_rows, x)
        h = _rmsnorm(u, lp["attn_norm"], c.norm_eps).astype(cdt)
        if i in full:
            c_q, latent, q_i, k_i, w = _project(h, positions, lp, c, cdt)
            index = (q_i, w, k_i)
        else:
            c_q, latent = _latent_project(h, positions, lp, c, cdt)
            k_i, index = None, selection
        on_layer(i, latent, k_i)
        # a full layer hands its selection on where a shared one follows
        keep = i in full and i + 1 < c.n_layers and i + 1 not in full
        o = _expanded_attention(c_q, positions, length, latent, lp, c, cdt, index=index,
                                h=h, keep=keep,
                                out_dtype=jnp.float32 if maps is None else cdt)
        if keep:
            o, selection = o
        if maps is None:
            x = _hc_out(x, o, None, cdt)
        else:
            x = _row_blocks(hc_out, T, ffn_rows, x, o, *maps)
        x = _row_blocks(functools.partial(ffn, lp), T, ffn_rows, x, real)
    return x


def make_forward_fn(config):
    """fn(params, tokens (T,) int32) -> logits (T, vocab) float32: the
    one-shot forward in the expanded form, no cache."""
    c = config
    cdt = jnp.dtype(c.dtype)

    def forward(params, tokens):
        x = jnp.take(params["embed_weight"], tokens, axis=0).astype(cdt)
        x = _sequence_layers(params, x, c, lambda *_: None)
        return _head(_merged(x, c), params, c, cdt)

    return jax.jit(forward)


def make_prefill_fn(config, page_size, mesh=None):
    """fn(params, cache, tokens (1, S_pad) int32, length () int32,
    pages (S_pad // page_size,) int32) -> (cache', logits (vocab,) float32).

    One whole prompt, padded to its bucket, through the expanded form with
    the selection as a mask (where there is an indexer); every layer's latent
    rows and index keys are written to the pages named (the padded tail's to the scratch page or to
    slots a later token overwrites before they are read, as the
    transformer's prefill leaves them).  Long prompts fit because the two
    things that grow with rows x keys, the index scores and the attention
    scores, are made a block of query rows and a chunk of keys at a time,
    and the FFN goes in row blocks too; blocks of the padded tail and chunks
    of keys ahead of a block's rows are skipped."""
    c = config
    cdt = jnp.dtype(c.dtype)
    page_size = int(page_size)
    if mesh is not None:
        raise NotImplementedError("mla_moe: no sharded bind; one chip holds "
                                  "its share of the experts")

    def prefill(params, cache, tokens, length, pages):
        n_pages = tokens.shape[1] // page_size
        emb = params["embed_weight"]
        x = jnp.take(emb, jnp.clip(tokens[0], 0, emb.shape[0] - 1), axis=0).astype(cdt)
        pools = {name: list(layers) for name, layers in cache.items()}

        def write(i, latent, k_i):
            with jax.named_scope("mx.gen.pool_write"):
                for name, rows, at in (("latent", latent, i),
                                       ("index", k_i, _index_rank(c, i))):
                    if name not in pools or rows is None:
                        continue
                    paged = rows.reshape(n_pages, page_size, -1)
                    pools[name][at] = pools[name][at].at[pages].set(
                        paged.astype(pools[name][at].dtype))

        x = _sequence_layers(params, x, c, write, length)
        last = lax.dynamic_index_in_dim(x, length - 1, axis=0, keepdims=True)
        return pools, _head(_merged(last, c), params, c, cdt)[0]

    return prefill


def make_decode_fn(config, slots, max_pages_per_slot, page_size,
                   block_k=None, mesh=None):
    """fn(params, cache, tokens (S,), positions (S,), block_tables
    (S, max_pages_per_slot), active (S,)) -> (cache', (logits (S, vocab)
    float32, counters (len(decode_counters(config)),) int32)).

    One token a slot: its latent row and index key are written in place at
    ``block_tables[b, positions[b] // page_size]``; the slot's cached index
    keys are scored, the ``index_topk`` best causal positions kept
    (``lax.top_k``), their latent rows gathered through the block table, and
    attended in the absorbed form; a layer that shares an earlier layer's
    selection gathers its own rows at the ids that layer kept.  Without the
    indexer every cached row of the slot is attended through
    :func:`paged_attention`, ``block_k`` rows a turn (default
    ``DECODE_BLOCK_K``).  Inactive slots write to the scratch page, attend
    nothing that counts and get zero logits."""
    c = config
    cdt = jnp.dtype(c.dtype)
    page_size = int(page_size)
    max_ctx = int(max_pages_per_slot) * page_size
    topk = min(c.index_topk, max_ctx)
    block_k = int(block_k or DECODE_BLOCK_K)
    scale = _softmax_scale(c)
    names = decode_counters(c)
    full = _full_layers(c)
    if mesh is not None:
        raise NotImplementedError("mla_moe: no sharded bind; one chip holds "
                                  "its share of the experts")

    def select(cache, f, q_i, w, lengths, block_tables):
        """The slot's ``topk`` cached positions of largest index score (S,
        topk) and which of them hold a key."""
        S = q_i.shape[0]
        with jax.named_scope("mx.gen.index"):
            keys = cache["index"][f][block_tables].reshape(S, max_ctx, -1)
            valid = jnp.arange(max_ctx)[None, :] < lengths[:, None]
            scores = jnp.where(valid, _index_scores(q_i, w, keys), -jnp.inf)
            top, chosen = lax.top_k(scores, topk)                # (S, topk)
            kept = top > -jnp.inf
        return chosen, kept

    def attend(cache, i, c_q, chosen, kept, positions, block_tables, lp, gate):
        q_nope, q_rope = _queries(c_q, positions, lp, c, cdt)
        with jax.named_scope("mx.gen.attn"):
            page = jnp.take_along_axis(block_tables, chosen // page_size, axis=1)
            rows = cache["latent"][i].reshape(-1, _latent_width(c))[
                page * page_size + chosen % page_size]           # (S, topk, w)
            c_kv = rows[..., :c.kv_rank]
            k_rope = rows[..., c.kv_rank:c.kv_rank + c.d_rope]
            kv_b = lp["kv_b_weight"].astype(cdt)
            q_lat = jnp.einsum("she,rhe->shr", q_nope, kv_b[..., :c.d_nope],
                               preferred_element_type=jnp.float32).astype(cdt)
            s = (jnp.einsum("shr,skr->shk", q_lat, c_kv,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("she,ske->shk", q_rope, k_rope,
                              preferred_element_type=jnp.float32)) * scale
            s = jnp.where(kept[:, None, :], s, -jnp.inf)
            if "attn_sink" in lp:
                # exp(s) / (exp(sink) + sum exp(s)); an inactive slot's row
                # is all -inf and comes out all 0
                sink = lp["attn_sink"].astype(jnp.float32)[None, :]     # (1, H)
                top = jnp.maximum(jnp.max(s, axis=-1), sink)
                e = jnp.exp(s - top[..., None])
                p = e / (jnp.sum(e, axis=-1) + jnp.exp(sink - top))[..., None]
            else:
                # an inactive slot keeps nothing: its row is all -inf
                p = jnp.where(kept[:, None, :], jax.nn.softmax(s, axis=-1), 0.0)
            o_lat = jnp.einsum("shk,skr->shr", p.astype(cdt), c_kv,
                               preferred_element_type=jnp.float32).astype(cdt)
            o = jnp.einsum("shr,rhe->she", o_lat, kv_b[..., c.d_nope:],
                           preferred_element_type=jnp.float32)
            if gate is not None:
                o = o * gate
        return _output(o, lp, cdt), jnp.sum(kept)

    def decode(params, cache, tokens, positions, block_tables, active):
        emb = params["embed_weight"]
        x = jnp.take(emb, jnp.clip(tokens, 0, emb.shape[0] - 1), axis=0).astype(cdt)
        x = _streams(x, c)
        page = jnp.take_along_axis(block_tables, (positions // page_size)[:, None],
                                   axis=1)[:, 0]
        page = jnp.where(active, page, 0)        # inactive slots write to scratch
        offset = positions % page_size
        lengths = jnp.where(active, positions + 1, 0)
        pools = {name: list(layers) for name, layers in cache.items()}
        total = {k: jnp.int32(0) for k in names}
        selection = None           # the latest full layer's kept ids
        for i in range(c.n_layers):
            lp = _layer(params, i, c)

            def attention(u, i=i, lp=lp):
                nonlocal selection
                h = _rmsnorm(u, lp["attn_norm"], c.norm_eps).astype(cdt)
                f = _index_rank(c, i)      # None: no indexer of its own
                if f is not None:
                    c_q, latent, q_i, k_i, w = _project(h, positions, lp, c, cdt)
                else:
                    c_q, latent = _latent_project(h, positions, lp, c, cdt)
                with jax.named_scope("mx.gen.pool_write"):
                    pools["latent"][i] = pools["latent"][i].at[page, offset].set(
                        latent.astype(pools["latent"][i].dtype))
                    if f is not None:
                        pools["index"][f] = pools["index"][f].at[page, offset].set(
                            k_i.astype(pools["index"][f].dtype))
                if not c.indexer:
                    q_nope, q_rope = _queries(c_q, positions, lp, c, cdt)
                    return paged_attention(pools["latent"][i], q_nope, q_rope, lengths,
                                           block_tables, lp, c, cdt, block_k), None
                if f is not None:
                    selection = select(pools, f, q_i, w, lengths, block_tables)
                gate = _gate(h, lp, c, cdt) if "o_gate_weight" in lp else None
                return attend(pools, i, c_q, *selection, positions, block_tables, lp,
                              gate)

            x, selected = _residual(x, attention, lp, "attn", c, cdt)
            x, counts = _residual(x, lambda u, lp=lp: _ffn(u, lp, c, cdt, active),
                                  lp, "ffn", c, cdt)
            if not c.indexer:
                counts["attn_rows_read"] = jnp.sum(lengths)
            elif i in full:
                counts["dsa_keys_scanned"] = jnp.sum(lengths)
                counts["dsa_keys_selected"] = selected
            else:
                counts["dsa_keys_selected"] = selected
                counts[REUSED_DECODE_COUNTER] = jnp.sum(active)
            for k, v in counts.items():
                total[k] = total[k] + v.astype(jnp.int32)
        logits = jnp.where(active[:, None], _head(_merged(x, c), params, c, cdt), 0.0)
        counters = jnp.stack([total[k] for k in names])
        return pools, (logits, counters)

    return decode
