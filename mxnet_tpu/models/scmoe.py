"""Decoder LM of shortcut-connected double blocks: two latent attentions, two
dense SwiGLU FFNs and one expert layer beside them in every layer, softmax
top-k routing over real and identity ("zero-compute") experts, dense latent
attention over the whole paged cache; for incremental decode through
:class:`~mxnet_tpu.serving.GenerativePredictor` on a chip that holds its
share of the experts.

The pieces that are :mod:`~mxnet_tpu.models.mla_moe`'s are imported from
there (norm, rotary, products, SwiGLU, the latent projections and their
cache row, the expanded prefill attention, the held-experts loop); what is
this block's own is here.  ``N`` is RMSNorm (eps ``norm_eps``) with a gain of
its own wherever it stands; the residual is in the compute type, products
are in it with float32 accumulation, norms, rotary, softmax and the router
are float32.

- **double layer** ``l`` on input ``x``::

      x1 = x  + MLA[l,0](N(x))          h1 = N(x1)     m = MoE[l](h1)
      x2 = x1 + FFN[l,0](h1)
      x3 = x2 + MLA[l,1](N(x2))         h3 = N(x3)
      x4 = x3 + FFN[l,1](h3) + m

  The expert layer reads what the first attention left and is added after
  the second FFN: it runs *beside* the first FFN, the second attention and
  the second FFN (the shortcut).  ``FFN`` is SwiGLU of width ``d_ff``.
- **MLA** (DeepSeek-V2's, with the low-rank scalings): ``c_q = N(h W_qa)``;
  ``[q_nope | q_rope] = c_q W_qb`` a head, both times ``(d_model /
  q_rank)^1/2``; ``[c_kv | k_rope] = h W_kva``, ``c_kv = N(c_kv) (d_model /
  kv_rank)^1/2``, ``k_rope`` unscaled; interleaved rotary on ``q_rope`` and
  on the one ``k_rope``; ``k_nope = c_kv W_kb``, ``v = c_kv W_vb`` a head;
  causal softmax of ``(q_nope . k_nope + q_rope . k_rope) / (d_nope +
  d_rope)^1/2`` over **all** earlier positions; output ``H x d_v -> d``.
  The cache holds the scaled ``[c_kv | k_rope]`` padded to whole 128-lane
  tiles, one array a sublayer (``2 n_layers`` arrays).  Prefill runs the
  expanded form, decode the absorbed one: the query ``[q_nope W_kb^T |
  q_rope | 0]`` of every head against the cached rows, in key blocks with
  an online softmax, blocks past the longest slot skipped and nothing of
  (slots x max_ctx x row) materialised (``mla_moe.paged_attention``: on a
  TPU the Pallas kernel ``kernels/mla_paged_decode.py`` reads the pages
  where they lie, elsewhere ``blocked_attention`` gathers a block of pages
  a turn).
- **MoE**: ``p = softmax(h1 W_r)`` in float32 over ``n_experts +
  n_zero_experts`` outputs; the ``experts_per_token`` largest of ``p + b`` are
  chosen (``b`` moves the choice only); ``g_i = route_scale p_i``, not
  renormalised; ``m = sum_{i chosen, i < n_experts, i held} g_i SwiGLU_i(h1)
  + (sum_{i chosen, i >= n_experts} g_i) h1``.  The identity term needs no
  weight and no exchange, so it is whole here; what absent experts would add
  is left out; an expert no token chose is skipped.  No shared expert.
- **head**: ``N``, then an untied head over the rows of the vocabulary held.

Entry points are those ``GenerativePredictor`` asks a model module for
(``init_kv_cache``, ``kv_page_bytes``, ``make_prefill_fn``,
``make_decode_fn``, ``decode_counters``, ``_decode_block_k``);
``make_forward_fn`` is the cache-free one-shot forward.

Assumed where the published configuration gives switches and numbers only
(the order of the block, where the two low-rank factors go, softmax before
the bias, no renormalisation, ``zero_expert_type`` identity): the public
``transformers`` implementation and the technical report; the benchmark's
configuration file lists each under ``assumed``.  Departures: the key-value
up-projection is kept as its two halves; no multi-token-prediction layer; the
expert branch is computed in line, not overlapped with what it runs beside;
no loss, no backward pass, no mesh, no extend program.  Measured: against
``benchmark/reference/longcat_lm.py`` at tiny widths on the CPU
(``tests/test_scmoe.py``) and at the published widths on a TPU v5e by the
cell ``longcat-flash.decode-pool-12k`` (PERF.md sections 2, 5 and 6).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import mla_moe as mm
from .mla_moe import DECODE_BLOCK_K, _rmsnorm, _swiglu
from .mla_moe import _decode_block_k  # noqa: F401  (the predictor asks for it)

__all__ = ["ShortcutMoEConfig", "init_params", "init_kv_cache", "kv_page_bytes",
           "make_prefill_fn", "make_decode_fn", "make_forward_fn", "decode_counters",
           "DECODE_COUNTERS"]

# what one decode step counts on the device, summed over its layers and
# returned beside the logits (``profiler.generate_record`` names)
DECODE_COUNTERS = ("moe_pairs_held", "moe_tokens", "moe_experts_touched",
                   "moe_pairs_at_max_load", "moe_pairs_zero", "attn_rows_read")


def decode_counters(config):
    """Names of what the decode program counts, the same for every
    configuration."""
    return DECODE_COUNTERS


@dataclasses.dataclass
class ShortcutMoEConfig:
    vocab: int = 16384              # rows of the vocabulary held here
    d_model: int = 6144
    n_heads: int = 64
    n_layers: int = 4               # double layers
    d_ff: int = 12288               # a dense FFN's width
    d_expert: int = 2048            # a routed expert's width
    n_experts: int = 512            # real experts the router knows
    n_zero_experts: int = 256       # identity experts after them
    experts_per_token: int = 12
    held_experts: tuple = tuple(range(16))   # ids of the real experts held here
    route_scale: float = 6.0
    q_rank: int = 1536
    kv_rank: int = 512
    d_nope: int = 128
    d_rope: int = 64
    d_v: int = 128
    scale_q_lora: bool = True       # queries times (d_model / q_rank)^1/2
    scale_kv_lora: bool = True      # c_kv times (d_model / kv_rank)^1/2
    rope_theta: float = 1e7
    norm_eps: float = 1e-5
    max_len: int = 131072
    dtype: str = "bfloat16"         # weights as given; cache and products
    # the module whose programs serve this configuration
    module: str = "mxnet_tpu.models.scmoe"

    def __post_init__(self):
        self.held_experts = tuple(int(e) for e in self.held_experts)

    @property
    def q_scale(self):
        return (self.d_model / self.q_rank) ** 0.5 if self.scale_q_lora else None

    @property
    def kv_scale(self):
        return (self.d_model / self.kv_rank) ** 0.5 if self.scale_kv_lora else None


def sublayer_shapes(config):
    """The leaves of one attention with the dense FFN that follows it."""
    c = config
    d, H = c.d_model, c.n_heads
    return {
        "attn_norm": ((d,), "ones"),
        "ffn_norm": ((d,), "ones"),
        "q_a_weight": ((d, c.q_rank), "normal"),
        "q_a_norm": ((c.q_rank,), "ones"),
        "q_b_weight": ((c.q_rank, H, c.d_nope + c.d_rope), "normal"),
        "kv_a_weight": ((d, c.kv_rank + c.d_rope), "normal"),
        "kv_a_norm": ((c.kv_rank,), "ones"),
        "k_b_weight": ((c.kv_rank, H, c.d_nope), "normal"),
        "v_b_weight": ((c.kv_rank, H, c.d_v), "normal"),
        "o_weight": ((H, c.d_v, d), "normal"),
        "dense_gate_weight": ((d, c.d_ff), "normal"),
        "dense_up_weight": ((d, c.d_ff), "normal"),
        "dense_down_weight": ((c.d_ff, d), "normal"),
    }


def expert_layer_shapes(config):
    """The leaves of one expert layer: the router over real and identity
    experts, and the held experts' matrices stacked (held, ...)."""
    c = config
    d, Eh = c.d_model, len(c.held_experts)
    R = c.n_experts + c.n_zero_experts
    return {
        "router_weight": ((d, R), "normal"),
        "router_bias": ((R,), "bias"),
        "expert_gate_weight": ((Eh, d, c.d_expert), "normal"),
        "expert_up_weight": ((Eh, d, c.d_expert), "normal"),
        "expert_down_weight": ((Eh, c.d_expert, d), "normal"),
    }


def param_shapes(config):
    """name -> (shape, kind): ``normal`` matrices, ``ones`` gains, ``bias``
    the router's correction bias.  Every leaf is one sublayer's own, named
    ``<leaf>.<layer>.<a>`` for attention ``a`` (0 or 1) of a double layer and
    the dense FFN after it, ``<leaf>.<layer>`` for its expert layer: nothing
    is stacked over layers, so no program slices a matrix out of a stack (the
    TPU's compiler copied every sliced matrix of the prefill).  The key-value
    up-projection is two leaves, its key and its value half."""
    c = config
    out = {"embed_weight": ((c.vocab, c.d_model), "normal"),
           "head_weight": ((c.vocab, c.d_model), "normal"),
           "final_norm": ((c.d_model,), "ones")}
    for l in range(c.n_layers):
        for a in (0, 1):
            out.update({"%s.%d.%d" % (k, l, a): v
                        for k, v in sublayer_shapes(c).items()})
        out.update({"%s.%d" % (k, l): v for k, v in expert_layer_shapes(c).items()})
    return out


def init_params(config, seed=0, scale=0.02, bias_scale=0.01):
    """Seeded float32 parameters on the host (tests and examples; the
    benchmark makes its own on the device)."""
    rng = np.random.RandomState(seed)
    out = {}
    for name, (shape, kind) in sorted(param_shapes(config).items()):
        if kind == "ones":
            out[name] = np.ones(shape, np.float32)
        else:
            out[name] = rng.normal(0.0, bias_scale if kind == "bias" else scale,
                                   shape).astype(np.float32)
    return {k: jnp.asarray(v) for k, v in out.items()}


# -- the cache ---------------------------------------------------------------
def init_kv_cache(config, num_pages, page_size, dtype=None):
    """Zeroed page pool, one array an attention under one block table:
    ``latent[2 l + a]`` (pages + 1, page, ``mla_moe._latent_width``) rows
    ``[c_kv | k_rope | 0]`` of sublayer ``a`` of double layer ``l``.  Page 0
    is the scratch page."""
    c = config
    cdt = jnp.dtype(dtype if dtype is not None else c.dtype)
    shape = (int(num_pages) + 1, int(page_size), mm._latent_width(c))
    return {"latent": [jnp.zeros(shape, cdt) for _ in range(2 * c.n_layers)]}


def kv_page_bytes(config, page_size):
    """Bytes one page holds over all ``2 n_layers`` attentions."""
    c = config
    return (2 * c.n_layers * int(page_size) * mm._latent_width(c)
            * jnp.dtype(c.dtype).itemsize)


# -- pieces ------------------------------------------------------------------
def _sublayer(params, c, l, a):
    """The leaves of attention ``a`` and dense FFN ``a`` of double layer ``l``."""
    return {k: params["%s.%d.%d" % (k, l, a)] for k in sublayer_shapes(c)}


def _expert_layer(params, l):
    """Router leaves of double layer ``l`` and its held experts' stacks, whole:
    an expert's matrices are sliced where they are used, inside the branch
    that may skip them."""
    return {"router_weight": params["router_weight.%d" % l],
            "router_bias": params["router_bias.%d" % l],
            "experts": (None,) + tuple(params["expert_%s_weight.%d" % (k, l)]
                                       for k in ("gate", "up", "down"))}


def _route(h, ep, c, active):
    """The router over real and zero experts, in float32: per token the
    chosen ids (T, k) and their gates ``route_scale * softmax`` (T, k); rows
    where ``active`` is false choose nothing (id -1, gate 0)."""
    with jax.named_scope("mx.lm.moe.route"):
        logits = jnp.einsum("td,de->te", h, ep["router_weight"].astype(h.dtype),
                            preferred_element_type=jnp.float32)
        p = jax.nn.softmax(logits, axis=-1)
        _top, ids = lax.top_k(p + ep["router_bias"].astype(jnp.float32),
                              c.experts_per_token)
        gates = c.route_scale * jnp.take_along_axis(p, ids, axis=-1)
        ids = jnp.where(active[:, None], ids, -1)
        gates = jnp.where(active[:, None], gates, 0.0)
    return ids, gates


def _moe(h, ep, c, cdt, active):
    """The expert layer's output ``m`` (T, d) float32 for normed rows ``h``:
    the identity experts' ``(sum of their gates) h`` and the held experts' on
    top; and what it counted."""
    ids, gates = _route(h, ep, c, active)
    with jax.named_scope("mx.lm.moe.zero"):
        zero = ids >= c.n_experts
        y = jnp.sum(jnp.where(zero, gates, 0.0), axis=-1)[:, None] * h.astype(jnp.float32)
    y, loads = mm._held_experts(h, ids, gates, ep["experts"], c, cdt, y)
    counts = mm._moe_counts(loads, active, c)
    counts["moe_pairs_zero"] = jnp.sum(zero)
    return y, counts


def _dense_ffn(h, sp, cdt):
    with jax.named_scope("mx.lm.ffn"):
        return _swiglu(h, sp["dense_gate_weight"], sp["dense_up_weight"],
                       sp["dense_down_weight"], cdt)


def _first_half(x1, real, sp, ep, c, cdt):
    """After the first attention, on residual rows ``x1`` (T, d): ``h1 =
    N(x1)`` feeds the expert layer and the first dense FFN; returns ``x2 =
    x1 + FFN(h1)``, the expert layer's ``m`` (float32, added after the second
    FFN) and its counts."""
    h1 = _rmsnorm(x1, sp["ffn_norm"], c.norm_eps).astype(cdt)
    m, counts = _moe(h1, ep, c, cdt, real)
    return x1 + _dense_ffn(h1, sp, cdt).astype(cdt), m, counts


def _second_half(x3, m, sp, c, cdt):
    """After the second attention: ``x4 = x3 + FFN(N(x3)) + m``."""
    h3 = _rmsnorm(x3, sp["ffn_norm"], c.norm_eps).astype(cdt)
    return x3 + (_dense_ffn(h3, sp, cdt) + m).astype(cdt)


def _attention_half(x, attend, a, sp, c, cdt):
    """``x + MLA[a](N(x))`` with ``attend(a, h, sp)`` prefill's or decode's."""
    h = _rmsnorm(x, sp["attn_norm"], c.norm_eps).astype(cdt)
    return x + attend(a, h, sp).astype(cdt)


# -- programs ----------------------------------------------------------------
def _sequence_layers(params, x, config, on_sublayer, length=None):
    """All double layers over one whole sequence ``x`` (T, d) at positions
    0..T-1, expanded attention; ``on_sublayer(index, latent)`` sees what a
    cache would hold.  Rows from ``length`` on (a prompt's padded tail) are
    carried along, not computed."""
    c = config
    cdt = jnp.dtype(c.dtype)
    T = x.shape[0]
    positions = jnp.arange(T)
    length = T if length is None else length
    real = positions < length
    ffn_rows = min(T, 1024)
    while T % ffn_rows:
        ffn_rows -= 1

    def first(xb, real, sp, ep):
        return lax.cond(real[0], lambda: _first_half(xb, real, sp, ep, c, cdt)[:2],
                        lambda: (xb, jnp.zeros(xb.shape, jnp.float32)))

    def second(xb, mb, real, sp):
        return lax.cond(real[0], lambda: _second_half(xb, mb, sp, c, cdt), lambda: xb)

    for l in range(c.n_layers):
        def attend(a, h, sp, l=l):
            c_q, latent = mm._latent_project(h, positions, sp, c, cdt, c.kv_scale)
            on_sublayer(2 * l + a, latent)
            return mm._expanded_attention(c_q, positions, length, latent, sp, c, cdt,
                                          q_scale=c.q_scale)

        sp0, sp1, ep = _sublayer(params, c, l, 0), _sublayer(params, c, l, 1), \
            _expert_layer(params, l)
        x = _attention_half(x, attend, 0, sp0, c, cdt)
        x, m = mm._row_blocks(functools.partial(first, sp=sp0, ep=ep), T, ffn_rows,
                              x, real)
        x = _attention_half(x, attend, 1, sp1, c, cdt)
        x = mm._row_blocks(functools.partial(second, sp=sp1), T, ffn_rows, x, m, real)
    return x


def make_forward_fn(config):
    """fn(params, tokens (T,) int32) -> logits (T, vocab) float32: the
    one-shot forward in the expanded form, no cache."""
    c = config
    cdt = jnp.dtype(c.dtype)

    def forward(params, tokens):
        x = jnp.take(params["embed_weight"], tokens, axis=0).astype(cdt)
        x = _sequence_layers(params, x, c, lambda *_: None)
        return mm._head(x, params, c, cdt)

    return jax.jit(forward)


def make_prefill_fn(config, page_size, mesh=None):
    """fn(params, cache, tokens (1, S_pad) int32, length () int32,
    pages (S_pad // page_size,) int32) -> (cache', logits (vocab,) float32).

    One whole prompt, padded to its bucket, through the expanded form; every
    attention's latent rows are written to the pages named (the padded
    tail's to the scratch page or to slots a later token overwrites before
    they are read).  Attention goes a block of query rows and a chunk of keys
    at a time and the per-row half in row blocks, as ``mla_moe``'s prefill."""
    c = config
    cdt = jnp.dtype(c.dtype)
    page_size = int(page_size)
    if mesh is not None:
        raise NotImplementedError("scmoe: no sharded bind; one chip holds its "
                                  "share of the experts")

    def prefill(params, cache, tokens, length, pages):
        n_pages = tokens.shape[1] // page_size
        emb = params["embed_weight"]
        x = jnp.take(emb, jnp.clip(tokens[0], 0, emb.shape[0] - 1), axis=0).astype(cdt)
        pools = list(cache["latent"])

        def write(i, latent):
            with jax.named_scope("mx.gen.pool_write"):
                paged = latent.reshape(n_pages, page_size, -1)
                pools[i] = pools[i].at[pages].set(paged.astype(pools[i].dtype))

        x = _sequence_layers(params, x, c, write, length)
        last = lax.dynamic_index_in_dim(x, length - 1, axis=0, keepdims=True)
        return {"latent": pools}, mm._head(last, params, c, cdt)[0]

    return prefill


def make_decode_fn(config, slots, max_pages_per_slot, page_size,
                   block_k=None, mesh=None):
    """fn(params, cache, tokens (S,), positions (S,), block_tables
    (S, max_pages_per_slot), active (S,)) -> (cache', (logits (S, vocab)
    float32, counters (len(DECODE_COUNTERS),) int32)).

    One token a slot: in each of the ``2 n_layers`` attentions its latent
    row is written in place at ``block_tables[b, positions[b] // page_size]``
    and every cached row of the slot up to it is attended in the absorbed
    form, ``block_k`` rows a turn (default ``DECODE_BLOCK_K``).  Inactive
    slots write to the scratch page, attend nothing and get zero logits."""
    c = config
    cdt = jnp.dtype(c.dtype)
    page_size = int(page_size)
    block_k = int(block_k or DECODE_BLOCK_K)
    if mesh is not None:
        raise NotImplementedError("scmoe: no sharded bind; one chip holds its "
                                  "share of the experts")

    def attention(pool, c_q, positions, lengths, block_tables, sp):
        q_nope, q_rope = mm._queries(c_q, positions, sp, c, cdt, c.q_scale)
        return mm.paged_attention(pool, q_nope, q_rope, lengths, block_tables, sp, c,
                                  cdt, block_k)

    def decode(params, cache, tokens, positions, block_tables, active):
        emb = params["embed_weight"]
        x = jnp.take(emb, jnp.clip(tokens, 0, emb.shape[0] - 1), axis=0).astype(cdt)
        page = jnp.take_along_axis(block_tables, (positions // page_size)[:, None],
                                   axis=1)[:, 0]
        page = jnp.where(active, page, 0)        # inactive slots write to scratch
        offset = positions % page_size
        lengths = jnp.where(active, positions + 1, 0)
        pools = list(cache["latent"])
        total = {k: jnp.int32(0) for k in DECODE_COUNTERS}
        for l in range(c.n_layers):
            def attend(a, h, sp, l=l):
                i = 2 * l + a
                c_q, latent = mm._latent_project(h, positions, sp, c, cdt, c.kv_scale)
                with jax.named_scope("mx.gen.pool_write"):
                    pools[i] = pools[i].at[page, offset].set(
                        latent.astype(pools[i].dtype))
                return attention(pools[i], c_q, positions, lengths, block_tables, sp)

            sp0, sp1 = _sublayer(params, c, l, 0), _sublayer(params, c, l, 1)
            x = _attention_half(x, attend, 0, sp0, c, cdt)
            x, m, counts = _first_half(x, active, sp0, _expert_layer(params, l), c, cdt)
            x = _attention_half(x, attend, 1, sp1, c, cdt)
            x = _second_half(x, m, sp1, c, cdt)
            counts["attn_rows_read"] = 2 * jnp.sum(lengths)
            for k, v in counts.items():
                total[k] = total[k] + v.astype(jnp.int32)
        logits = jnp.where(active[:, None], mm._head(x, params, c, cdt), 0.0)
        counters = jnp.stack([total[k] for k in DECODE_COUNTERS])
        return {"latent": pools}, (logits, counters)

    return decode
