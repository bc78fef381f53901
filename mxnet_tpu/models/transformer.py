"""Decoder-only transformer LM with explicit dp/tp/sp/ep SPMD sharding.

Reference counterpart: none architecturally (2017 predates transformers) —
this is the long-context / distributed flagship the survey mandates
(SURVEY §2.4, §5.7): the natural TPU generalization of the reference's
parallelism surface, exercising every mesh axis with *manual* SPMD
(`shard_map`) the way Megatron sharded layers map onto a TPU mesh:

- **dp**  batch sharding; gradient psum comes out of shard_map's
  unvarying-param transpose automatically.
- **mp**  megatron tensor parallel (ISSUE 20; ``tp`` is the legacy
  alias — whichever axis the mesh carries is resolved by
  :func:`_mp_axis`): vocab- and head-sharded embedding / qkv (column),
  row-parallel out-proj and ffn-down with ONE psum per block half —
  2 psums per block, asserted exact by
  :func:`block_collective_counts`.
- **sp**  sequence sharding with ring attention (parallel/ring.py) —
  K/V chunks ride ICI collective-permute while the MXU works.
- **ep**  expert parallel MoE ffn (soft top-k gating, experts sharded
  over ``ep``, combine via psum).

The attention core is the Pallas flash kernel (kernels/flash_attention.py)
when heads are local (tp/ulysses path) and the ring online-softmax when
sequence-sharded.

Pure-functional: ``init_params`` → flat dict, ``make_loss_fn`` returns a
shard_map'd scalar loss ready for ``jax.value_and_grad`` + pjit update
(spmd.TrainStep's functional cousin). Layer params are stacked over the
layer dim and scanned (one compiled block, XLA-friendly).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..context import kernel_platform
from ..util import shard_map as _shard_map

from ..parallel.ring import ring_attention_inner, full_attention

__all__ = ["TransformerConfig", "init_params", "param_specs", "make_loss_fn",
           "make_train_step", "make_forward_fn", "init_kv_cache",
           "make_prefill_fn", "make_decode_fn", "make_extend_fn",
           "draft_from_layers", "decode_schedule_shape",
           "block_collective_counts", "kv_cache_spec", "kv_page_bytes"]


def _mp_axis(axes):
    """The tensor-parallel axis this mesh carries: ``mp`` (ISSUE 20),
    falling back to the legacy ``tp`` alias; None when the mesh has
    neither (the replicated-model path)."""
    if "mp" in axes:
        return "mp"
    if "tp" in axes:
        return "tp"
    return None


@dataclasses.dataclass
class TransformerConfig:
    vocab: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_len: int = 2048
    n_experts: int = 0          # 0 → dense ffn; >0 → MoE every layer
    dtype: str = "bfloat16"     # compute dtype (params stay fp32)
    attn: str = "auto"          # auto|ring|ulysses|full
    remat: bool = False
    # flash-attention schedule parameters (ISSUE 10): None consults the
    # on-disk schedule table at trace time (tune.schedule_for, keyed by
    # this model's attention shape/dtype/backend) and falls back to a
    # block derived from the sequence; an explicit value pins the block
    attn_block_q: int | None = None
    attn_block_k: int | None = None


def init_params(config, seed=0):
    """Flat fp32 param dict; layer params stacked on a leading L dim."""
    c = config
    rng = np.random.RandomState(seed)
    dh = c.d_model // c.n_heads

    def norm(*shape, scale=0.02):
        return rng.normal(0.0, scale, shape).astype(np.float32)

    p = {
        "embed_weight": norm(c.vocab, c.d_model),
        "pos_embed_weight": norm(c.max_len, c.d_model),
        "final_ln_gamma": np.ones((c.d_model,), np.float32),
        "final_ln_beta": np.zeros((c.d_model,), np.float32),
    }
    L = c.n_layers
    p["ln1_gamma"] = np.ones((L, c.d_model), np.float32)
    p["ln1_beta"] = np.zeros((L, c.d_model), np.float32)
    p["ln2_gamma"] = np.ones((L, c.d_model), np.float32)
    p["ln2_beta"] = np.zeros((L, c.d_model), np.float32)
    p["attn_qkv_weight"] = norm(L, c.d_model, 3, c.n_heads, dh)
    p["attn_out_weight"] = norm(L, c.n_heads, dh, c.d_model)
    if c.n_experts:
        p["moe_gate_weight"] = norm(L, c.d_model, c.n_experts)
        p["ffn_up_weight"] = norm(L, c.n_experts, c.d_model, c.d_ff)
        p["ffn_down_weight"] = norm(L, c.n_experts, c.d_ff, c.d_model)
    else:
        p["ffn_up_weight"] = norm(L, c.d_model, c.d_ff)
        p["ffn_down_weight"] = norm(L, c.d_ff, c.d_model)
    return {k: jnp.asarray(v) for k, v in p.items()}


def param_specs(config, mesh):
    """PartitionSpec per param — megatron mp/tp + ep expert sharding.

    Column sharding (QKV heads, FFN-up output) and row sharding
    (attention out-proj input heads, FFN-down input) over the mesh's
    tensor-parallel axis (``mp``, or the legacy ``tp`` alias), the
    classic megatron split: each block needs exactly one psum after
    the attention out-proj and one after FFN-down."""
    ax = set(mesh.axis_names)
    tp = _mp_axis(ax)
    ep = "ep" if "ep" in ax else None
    sp = {
        "embed_weight": P(tp, None),
        "pos_embed_weight": P(),
        "final_ln_gamma": P(), "final_ln_beta": P(),
        "ln1_gamma": P(), "ln1_beta": P(), "ln2_gamma": P(), "ln2_beta": P(),
        "attn_qkv_weight": P(None, None, None, tp, None),
        "attn_out_weight": P(None, tp, None, None),
    }
    if config.n_experts:
        sp["moe_gate_weight"] = P()
        sp["ffn_up_weight"] = P(None, ep, None, tp)
        sp["ffn_down_weight"] = P(None, ep, tp, None)
    else:
        sp["ffn_up_weight"] = P(None, None, tp)
        sp["ffn_down_weight"] = P(None, tp, None)
    return sp


# What the layer scan saves for the backward (ISSUE 41). Autodiff of the
# float32 LayerNorm would keep float32 copies of activation size (x - mu,
# the normalised value, ...) and of jax.nn.relu a bool mask of its input,
# stacked over the layers. These VJPs keep the input as given (bfloat16 in
# training) with a float32 mean and rstd a row, and the ReLU's output, which
# ffn-down saves anyway. Forwards compute what they did, so programs that
# never differentiate (prefill, decode, extend) lower to the same operations.
def _ln(x, gamma, beta, eps):
    """(output, mean, rstd): the float32 arithmetic, op for op in the order
    the programs have always lowered it."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    centred = x32 - mu
    rstd = lax.rsqrt(var + eps)
    return ((centred * rstd) * gamma + beta).astype(x.dtype), mu, rstd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _layernorm(x, gamma, beta, eps=1e-5):
    return _ln(x, gamma, beta, eps)[0]


def _layernorm_fwd(x, gamma, beta, eps):
    y, mu, rstd = _ln(x, gamma, beta, eps)
    return y, (x, mu, rstd, gamma)


def _layernorm_bwd(eps, res, g):
    # gamma and beta are float32 (d,) here; their gradients sum the rows
    x, mu, rstd, gamma = res
    xhat = (x.astype(jnp.float32) - mu) * rstd
    g32 = g.astype(jnp.float32)
    rows = tuple(range(g.ndim - 1))
    gx = g32 * gamma
    dx = rstd * (gx - jnp.mean(gx, axis=-1, keepdims=True)
                 - xhat * jnp.mean(gx * xhat, axis=-1, keepdims=True))
    return (dx.astype(x.dtype), jnp.sum(g32 * xhat, axis=rows),
            jnp.sum(g32, axis=rows))


_layernorm.defvjp(_layernorm_fwd, _layernorm_bwd)


@jax.custom_vjp
def _relu(x):
    return jax.nn.relu(x)


def _relu_fwd(x):
    y = jax.nn.relu(x)
    return y, y


def _relu_bwd(y, g):
    return (jnp.where(y > 0, g, jnp.zeros_like(g)),)


_relu.defvjp(_relu_fwd, _relu_bwd)


def _attention(q, k, v, *, axes, causal=True, attn="auto", blocks=None):
    """(B, H_loc, S_loc, D) in, same out; sp handled per `attn` mode.
    ``blocks``: optional (block_q, block_k) flash schedule override —
    None entries consult the schedule table (kernels/flash_attention)."""
    has_sp = "sp" in axes
    if attn == "auto":
        attn = "ring" if has_sp else "flash"
    if not has_sp:
        # flash_attention runs the head dim as it is (a whole last dim is a
        # legal block), so head dims 64, 80, ... all take the O(S) kernel.
        # The materialized-scores reference is the cpu test path only:
        # kernel_platform() raises on any backend that is neither.
        if attn == "flash" and kernel_platform() == "tpu":
            from ..kernels import flash_attention
            bq, bk = blocks or (None, None)
            return flash_attention(q, k, v, causal=causal,
                                   block_q=bq, block_k=bk)
        return full_attention(q, k, v, causal=causal)
    if attn == "full":
        # debug mode: gather the whole sequence onto every sp shard and
        # attend globally (memory-heavy but exact); q keeps its shard
        idx = lax.axis_index("sp")
        kg = lax.all_gather(k, "sp", axis=2, tiled=True)
        vg = lax.all_gather(v, "sp", axis=2, tiled=True)
        return full_attention(q, kg, vg, causal=causal,
                              q_offset=idx * q.shape[2])
    if attn == "ring":
        return ring_attention_inner(q, k, v, axis_name="sp", causal=causal)
    if attn == "ulysses":
        from ..parallel.ring import ulysses_attention_inner
        return ulysses_attention_inner(q, k, v, axis_name="sp", causal=causal)
    if attn == "flash":
        raise ValueError(
            "attn='flash' attends only within the local shard and is "
            "incompatible with a sequence-parallel (sp) mesh axis; use "
            "'ring' or 'ulysses' (both use flash-style online softmax)")
    raise ValueError("unknown attn mode %r" % attn)


def _block(x, lp, c, axes, cdt):
    """One transformer block on local shards. lp: this layer's params."""
    with jax.named_scope("mx.lm.attn"):
        h = _layernorm(x, lp["ln1_gamma"], lp["ln1_beta"])
        qkv = jnp.einsum("bsd,dthe->tbhse", h,
                         lp["attn_qkv_weight"].astype(cdt))
        q, k, v = qkv[0], qkv[1], qkv[2]
        o = _attention(q, k, v, axes=axes, attn=c.attn,
                       blocks=(c.attn_block_q, c.attn_block_k))
        o = jnp.einsum("bhse,hed->bsd", o, lp["attn_out_weight"].astype(cdt))
        t = _mp_axis(axes)
        if t:
            o = lax.psum(o, t)         # row-parallel out-proj
        x = x + o
    return _ffn(x, lp, c, axes, cdt)


def _ffn(x, lp, c, axes, cdt):
    """The ffn half of a block (post-attention residual included) —
    shared verbatim between the training forward and the incremental
    decode step, so the two paths cannot drift numerically."""
    with jax.named_scope("mx.lm.ffn"):
        h = _layernorm(x, lp["ln2_gamma"], lp["ln2_beta"])
        t = _mp_axis(axes)
        if c.n_experts:
            gate = jax.nn.softmax(
                jnp.einsum("bsd,de->bse", h.astype(jnp.float32),
                           lp["moe_gate_weight"].astype(jnp.float32)), axis=-1)
            e_loc = lp["ffn_up_weight"].shape[0]
            e0 = lax.axis_index("ep") * e_loc if "ep" in axes else 0
            g_loc = lax.dynamic_slice_in_dim(gate, e0, e_loc, axis=-1).astype(cdt)
            up = jnp.einsum("bsd,edf->besf", h, lp["ffn_up_weight"].astype(cdt))
            act = _relu(up)
            down = jnp.einsum("besf,efd->besd", act,
                              lp["ffn_down_weight"].astype(cdt))
            f = jnp.einsum("besd,bse->bsd", down, g_loc)
            if "ep" in axes:
                f = lax.psum(f, "ep")
            if t:
                f = lax.psum(f, t)     # d_ff was also mp-sharded
        else:
            up = _relu(jnp.einsum("bsd,df->bsf", h,
                                  lp["ffn_up_weight"].astype(cdt)))
            f = jnp.einsum("bsf,fd->bsd", up, lp["ffn_down_weight"].astype(cdt))
            if t:
                f = lax.psum(f, t)     # row-parallel ffn-down
        return x + f


# The stacked matrices ``_block`` and ``_ffn`` multiply in the compute dtype
# (their ``.astype(cdt)``). ``_forward_local`` casts these before the layer
# scan and passes the casts as its ``xs``: cast inside the body, each layer's
# cast is saved again into a stack for the backward, and the weight gradients
# leave the loop as float32 stacks that are zeroed first. Cast outside, the
# backward reads the forward's stacks and writes compute-dtype gradients that
# the optimizer widens. The LayerNorm parameters and ``moe_gate_weight`` (the
# router runs in float32) stay float32.
_SCAN_CAST = frozenset(("attn_qkv_weight", "attn_out_weight",
                        "ffn_up_weight", "ffn_down_weight"))


def _forward_local(params, tokens, c, axes):
    """Local-shard forward → logits (B_loc, S_loc, V). tokens int32."""
    cdt = jnp.dtype(c.dtype)
    B, S_loc = tokens.shape

    # vocab(mp)-sharded embedding: mask + psum
    t = _mp_axis(axes)
    emb_w = params["embed_weight"]
    with jax.named_scope("mx.lm.embed"):
        v_loc = emb_w.shape[0]
        v0 = lax.axis_index(t) * v_loc if t else 0
        local_ids = tokens - v0
        in_range = (local_ids >= 0) & (local_ids < v_loc)
        x = jnp.take(emb_w, jnp.clip(local_ids, 0, v_loc - 1), axis=0)
        x = jnp.where(in_range[..., None], x, 0.0)
        if t:
            x = lax.psum(x, t)
        s0 = lax.axis_index("sp") * S_loc if "sp" in axes else 0
        pos = lax.dynamic_slice_in_dim(params["pos_embed_weight"], s0,
                                       S_loc, 0)
        x = (x + pos).astype(cdt)

    n_layers = params["ln1_gamma"].shape[0]

    def layer(x, lp):
        y = _block(x, lp, c, axes, cdt)
        return y, None

    if c.remat:
        layer = jax.checkpoint(layer)
    stacked = {k: v.astype(cdt) if k in _SCAN_CAST else v
               for k, v in params.items()
               if k not in ("embed_weight", "pos_embed_weight",
                            "final_ln_gamma", "final_ln_beta")}
    x, _ = lax.scan(layer, x, stacked)

    with jax.named_scope("mx.lm.head_loss"):
        x = _layernorm(x, params["final_ln_gamma"], params["final_ln_beta"])
        logits_loc = jnp.einsum("bsd,vd->bsv", x, emb_w.astype(cdt))
        if t:
            logits = lax.all_gather(logits_loc, t, axis=2, tiled=True)
        else:
            logits = logits_loc
        return logits.astype(jnp.float32)


def make_loss_fn(config, mesh, data_axes=("dp",)):
    """shard_map'd next-token CE loss(params, tokens) → scalar.

    tokens: (B, S+1) int32 global; batch shards over ``data_axes``, the
    sequence over ``sp`` when present. Gradients via ``jax.grad`` come
    back with `param_specs` shardings (shard_map transpose inserts the
    dp psum — the reference's KVStore push, now compiler-inserted).
    """
    c = config
    axes = set(mesh.axis_names)
    specs = param_specs(c, mesh)

    # every mesh axis the batch/sequence is split over must join the
    # loss psum (incl. a multi-host "dcn" axis ahead of dp)
    reduce_axes = tuple(a for a in mesh.axis_names
                        if a in set(data_axes) | {"sp"})

    def local_loss(params, tokens):
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        logits = _forward_local(params, inp, c, axes)
        with jax.named_scope("mx.lm.head_loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, tgt[..., None].astype(jnp.int32), axis=-1)[..., 0]
            loss_sum = jnp.sum(nll)
            count = jnp.float32(nll.size)
            if reduce_axes:
                loss_sum = lax.psum(loss_sum, reduce_axes)
                count = lax.psum(count, reduce_axes)
            return loss_sum / count

    # tokens enter with seq split over sp: shard (B_loc, S_loc + 1) needs
    # the +1 target shift *before* sharding — handled by passing the full
    # sequence and slicing locally with a halo exchange. Simpler exact
    # scheme: shard tokens (B, S+1) over batch only, slice seq inside.
    def local_loss_seqsplit(params, tokens):
        if "sp" not in axes:
            return local_loss(params, tokens)
        n_sp = lax.psum(1, "sp")
        idx = lax.axis_index("sp")
        S = tokens.shape[1] - 1
        s_loc = S // n_sp
        my = lax.dynamic_slice_in_dim(tokens, idx * s_loc, s_loc + 1, 1)
        return local_loss(params, my)

    # tokens enter sharded over batch only; the sequence (+1 target
    # overlap) is sliced per-sp-shard inside local_loss_seqsplit
    token_spec = P(tuple(a for a in data_axes if a in axes) or None, None)

    def loss_fn(params, tokens):
        sp_params = {k: specs[k] for k in params}
        return _shard_map(
            local_loss_seqsplit, mesh=mesh,
            in_specs=(sp_params, token_spec), out_specs=P(),
            check_vma=False,
        )(params, tokens)

    return loss_fn, specs


def make_train_step(config, mesh, optimizer=None, data_axes=("dp",)):
    """Fused SPMD train step: loss + grad + sgd-momentum update, jitted
    with NamedShardings from `param_specs` (spmd.TrainStep's functional
    twin for the transformer family)."""
    from ..parallel.spmd import functional_optimizer, FunctionalOptimizer

    opt = optimizer or functional_optimizer("sgd", learning_rate=0.1,
                                            momentum=0.9)
    if isinstance(opt, dict):
        opt = functional_optimizer(**opt)
    assert isinstance(opt, FunctionalOptimizer)
    loss_fn, specs = make_loss_fn(config, mesh, data_axes)

    def step(carry, tokens):
        params, opt_state, n = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        with jax.named_scope("mx.opt.update"):
            new_p, new_s = opt.apply(params, grads, opt_state, n)
        return (new_p, new_s, n + 1), loss

    shardings = {k: NamedSharding(mesh, s) for k, s in specs.items()}
    rep = NamedSharding(mesh, P())

    def place(params):
        opt_state = opt.init(params)
        params = {k: jax.device_put(v, shardings[k]) for k, v in params.items()}
        opt_state = {k: jax.tree_util.tree_map(
            lambda x: jax.device_put(x, shardings[k]), v)
            for k, v in opt_state.items()}
        return (params, opt_state, jax.device_put(jnp.zeros((), jnp.int32),
                                                  rep))

    # The carry leaves the step laid out exactly as place() put it in.
    # Left to the compiler, the outputs come back under differently
    # spelled (equivalent) shardings and the second call compiles the
    # whole step again (seen on the chip: 6 s of a 21 s phase).
    # (only the optimizer state's tree structure is needed, so scalars
    # stand in for the params: param_specs names exactly init_params' keys)
    like = {k: jax.ShapeDtypeStruct((), jnp.float32) for k in shardings}
    opt_sh = {k: jax.tree_util.tree_map(lambda _: shardings[k], v)
              for k, v in jax.eval_shape(opt.init, like).items()}
    return jax.jit(step, donate_argnums=(0,),
                   out_shardings=((shardings, opt_sh, rep), rep)), place


# ---------------------------------------------------------------------------
# collective accounting (ISSUE 20): the megatron sharding's contract is
# ONE psum per block half — 2 per transformer block. Assert it from the
# traced jaxpr, not the compiled HLO: the count is backend-independent
# and survives the CPU pipeline's CSE/barrier stripping that makes HLO
# text counting unstable (the PR 19 lesson).
# ---------------------------------------------------------------------------
def _sub_jaxprs(eqn):
    from jax.extend import core as _core

    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if isinstance(x, _core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, _core.Jaxpr):
                yield x


def _count_prims(jaxpr, names):
    n = {k: 0 for k in names}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in n:
            n[eqn.primitive.name] += 1
        for sub in _sub_jaxprs(eqn):
            for k, v in _count_prims(sub, names).items():
                n[k] += v
    return n


def _scan_bodies(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn.params["jaxpr"].jaxpr
        else:
            for sub in _sub_jaxprs(eqn):
                yield from _scan_bodies(sub)


def block_collective_counts(config, mesh, data_axes=("dp",)):
    """Per-step collective bill of the shard_map'd loss forward, from
    the traced jaxpr: ``psum_per_block`` counts psums inside the
    scanned transformer-block body (exactly 2 on an mp mesh — the
    attention out-proj and ffn-down row-parallel reductions; 0 when
    the model is replicated), ``psum_outside`` the psums outside the
    scan (vocab-sharded embedding + the dp/sp loss reductions), and
    ``all_gather`` the logit gathers. Feeds ``profiler.mp_record`` and
    the exactness assert in tests/test_model_parallel.py."""
    loss_fn, _specs = make_loss_fn(config, mesh, data_axes)
    params = jax.eval_shape(lambda: init_params(config))
    B = int(np.prod([s for a, s in zip(mesh.axis_names, mesh.devices.shape)
                     if a in data_axes]) or 1)
    tokens = jax.ShapeDtypeStruct((B, 9), jnp.int32)
    jaxpr = jax.make_jaxpr(loss_fn)(params, tokens).jaxpr
    bodies = list(_scan_bodies(jaxpr))
    per_block = max((_count_prims(b, ("psum",))["psum"] for b in bodies),
                    default=0)
    total = _count_prims(jaxpr, ("psum", "all_gather"))
    return {
        "psum_per_block": per_block,
        "psum_outside": total["psum"] - per_block * len(bodies),
        "all_gather": total["all_gather"],
        "n_blocks": config.n_layers,
    }


def kv_cache_spec(mesh):
    """PartitionSpec of the paged KV cache (L, 2, P+1, page, H*Dh) on an
    mp mesh: the head-major lane axis sharded over the tensor-parallel
    axis, so each chip holds the lanes of its own H/mp heads of every page
    (the sharded-serving-group memory claim). Replicated when the mesh has
    no mp/tp axis."""
    t = _mp_axis(set(mesh.axis_names))
    return P(None, None, None, None, t)


# ---------------------------------------------------------------------------
# PAGED per-layer KV cache. The serving tier (serving/generate.py) owns
# page allocation and batch-slot bookkeeping; the functions here are the
# pure compiled programs:
#
# - ``make_forward_fn``      one-shot logits (B, S, V) on a single
#                            device — the numerical reference the decode
#                            path must match per token.
# - ``init_kv_cache``        the cache buffer: (L, 2, P, page, H*Dh). A
#                            page of one layer is one contiguous
#                            (page, H*Dh) slab holding every head: the
#                            two minor dims fill the TPU's (16, 128)
#                            bfloat16 tiles whatever the head dim, so the
#                            device keeps the array in this order (with
#                            (..., H, Dh) and Dh = 64 it made the page
#                            index the minor dim and every access a
#                            relayout). Page 0 is the SCRATCH page —
#                            never handed out by the allocator; inactive
#                            slots and padded prompt tail positions write
#                            there.
# - ``make_prefill_fn``      causal forward over one padded prompt that
#                            scatters every position's K/V into its
#                            page (block-table order) and returns the
#                            last valid position's logits — the first
#                            generated token comes out of prefill.
# - ``make_decode_fn``       one token per active batch slot. The layer
#                            loop CARRIES the whole pool: each layer
#                            writes the token's K/V row in place at
#                            [layer, kv, page, offset] derived from its
#                            position, then attends the slot's pages. On
#                            a TPU that is the Pallas kernel
#                            ``kernels/paged_decode.py``: it walks the
#                            block table, copies each page from the pool
#                            where it lies and stops after the page that
#                            holds the slot's position, so a step reads
#                            the valid keys and values and nothing else.
#                            The CPU test path gathers the slot's pages
#                            and runs ``_paged_decode_attention``, the
#                            same online softmax in lax and the kernel's
#                            reference in the tests. ``block_k`` (key
#                            columns per softmax turn) is consulted from
#                            the PR 10 schedule table at trace time
#                            (decode-shape key: seq_q == 1, causal == 0 —
#                            the decode query attends to ALL cached keys,
#                            masked by length, not by the kernel's causal
#                            row>=col rule).
# - ``make_extend_fn``       several tokens per slot (prefix-tail
#                            prefill, speculative verify): still scans
#                            the pool and gathers the slot's pages.
#
# The attention math mirrors kernels/flash_attention.py's online
# softmax (running max / denominator / unnormalized accumulator, fp32),
# so prefill+decode logits match the one-shot forward to
# accumulation-order tolerance — asserted in tests/test_generate.py.
# ---------------------------------------------------------------------------
def make_forward_fn(config):
    """Single-device one-shot logits fn(params, tokens (B, S) int32) →
    (B, S, V) fp32 — ``make_loss_fn``'s mesh-free twin (the serving
    parity reference and the prefill program's ancestor)."""
    c = config

    def fwd(params, tokens):
        return _forward_local(params, tokens, c, frozenset())

    return jax.jit(fwd)


def init_kv_cache(config, num_pages, page_size, dtype=None):
    """Zeroed paged KV cache (n_layers, 2, num_pages + 1, page_size,
    n_heads * head_dim) in the compute dtype, head h in lanes
    ``[h * head_dim, (h + 1) * head_dim)``. Index 0 on the page axis
    is the scratch page (see module comment); callers allocate real
    page ids from 1..num_pages."""
    c = config
    cdt = jnp.dtype(dtype if dtype is not None else c.dtype)
    dh = c.d_model // c.n_heads
    return jnp.zeros((c.n_layers, 2, int(num_pages) + 1, int(page_size),
                      c.n_heads * dh), cdt)


def decode_schedule_shape(config, slots, max_ctx):
    """The schedule-table key shape the decode step consults:
    (batch=slots, heads, seq_q=1, seq_k=max_ctx, head_dim, causal=0) —
    the same convention the flash-attention consult uses, so the
    tune_kernels decode-shape sweep populates exactly this key."""
    c = config
    return (int(slots), c.n_heads, 1, int(max_ctx),
            c.d_model // c.n_heads, 0)


def _decode_block_k(config, slots, max_ctx):
    """Trace-time consult for the decode attention chunk size."""
    from ..kernels.flash_attention import DEFAULT_BLOCK
    from ..tune import schedule_for

    sched = schedule_for("flash_attention",
                         decode_schedule_shape(config, slots, max_ctx),
                         str(jnp.dtype(config.dtype))) or {}
    block_k = int(sched.get("block_k", DEFAULT_BLOCK))
    return max(1, min(block_k, int(max_ctx)))


def _paged_decode_attention(q, k, v, positions, block_k):
    """Flash-style blocked decode attention for one query token per
    slot. q: (B, H, 1, Dh); k/v: (B, H, L, Dh) gathered from the page
    pool (L = max_pages_per_slot * page_size); key column j of slot b
    is valid iff j <= positions[b] (the slot's own token was written
    before the call). Online softmax over ``block_k``-column chunks —
    the flash forward kernel's loop in lax, so per-slot dynamic
    lengths mask exactly."""
    B, H, L, Dh = k.shape
    scale = 1.0 / (Dh ** 0.5)
    nb = -(-L // block_k)
    pad = nb * block_k - L
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    q32 = q[:, :, 0, :].astype(jnp.float32) * scale          # (B, H, Dh)
    neg = jnp.float32(-1e30)

    def body(j, carry):
        m, l, acc = carry
        kb = lax.dynamic_slice_in_dim(k, j * block_k, block_k,
                                      axis=2).astype(jnp.float32)
        vb = lax.dynamic_slice_in_dim(v, j * block_k, block_k,
                                      axis=2).astype(jnp.float32)
        s = jnp.einsum("bhd,bhkd->bhk", q32, kb,
                       preferred_element_type=jnp.float32)
        cols = j * block_k + jnp.arange(block_k)
        ok = cols[None, None, :] <= positions[:, None, None]
        s = jnp.where(ok, s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhk,bhkd->bhd", p, vb, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((B, H), neg, jnp.float32)
    l0 = jnp.zeros((B, H), jnp.float32)
    a0 = jnp.zeros((B, H, Dh), jnp.float32)
    _, l, acc = lax.fori_loop(0, nb, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out[:, :, None, :].astype(q.dtype)                # (B, H, 1, Dh)


def _paged_extend_attention(q, k, v, positions, block_k):
    """:func:`_paged_decode_attention` generalized to T query tokens per
    slot (ISSUE 16). q: (B, H, T, Dh); k/v: (B, H, L, Dh) gathered from
    the page pool; query row t of slot b sits at ``positions[b, t]`` and
    attends key column j iff ``j <= positions[b, t]`` — the per-row
    causal mask that makes one batched call serve both the shared-prefix
    tail prefill (rows are consecutive prompt-tail positions attending
    the cached prefix pages) and the speculative verify step (rows are
    the pending token + k draft proposals). Same fp32 online softmax."""
    B, H, L, Dh = k.shape
    scale = 1.0 / (Dh ** 0.5)
    nb = -(-L // block_k)
    pad = nb * block_k - L
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    T = q.shape[2]
    q32 = q.astype(jnp.float32) * scale                      # (B, H, T, Dh)
    neg = jnp.float32(-1e30)

    def body(j, carry):
        m, l, acc = carry
        kb = lax.dynamic_slice_in_dim(k, j * block_k, block_k,
                                      axis=2).astype(jnp.float32)
        vb = lax.dynamic_slice_in_dim(v, j * block_k, block_k,
                                      axis=2).astype(jnp.float32)
        s = jnp.einsum("bhtd,bhkd->bhtk", q32, kb,
                       preferred_element_type=jnp.float32)
        cols = j * block_k + jnp.arange(block_k)
        ok = cols[None, None, None, :] <= positions[:, None, :, None]
        s = jnp.where(ok, s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhtk,bhkd->bhtd", p, vb, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((B, H, T), neg, jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    a0 = jnp.zeros((B, H, T, Dh), jnp.float32)
    _, l, acc = lax.fori_loop(0, nb, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)                               # (B, H, T, Dh)


def _stacked_layer_params(params):
    return {k: v for k, v in params.items()
            if k not in ("embed_weight", "pos_embed_weight",
                         "final_ln_gamma", "final_ln_beta")}


def make_prefill_fn(config, page_size, mesh=None):
    """fn(params, cache, tokens (1, S_pad) int32, length () int32,
    pages (S_pad // page_size,) int32) → (cache', logits (V,) fp32).

    ``mesh``: the replica group's mesh when the program is bound sharded
    (``GenerativePredictor(mesh=)``). Everything but attention is plain
    jnp that GSPMD partitions; attention runs under ``shard_map`` over the
    heads the mesh's mp axis already shards, because a Mosaic kernel
    cannot be partitioned automatically (on the chip the lowering raises
    "Mosaic kernels cannot be automatically partitioned").

    Runs the SAME causal block forward as ``make_forward_fn`` over the
    padded prompt (so flash/full attention and its schedule consult are
    shared), scatters each position p's K/V into
    ``cache[layer, :, pages[p // page_size], p % page_size]``, and
    returns the logits of position ``length - 1``. Padded tail
    positions write garbage K/V into whatever page their index names —
    callers pad ``pages`` with 0, the scratch page, past the allocated
    prompt pages; garbage inside an allocated page at offsets >=
    length is never attended (decode masks columns > position) and is
    overwritten before the position is reached."""
    c = config
    cdt = jnp.dtype(c.dtype)
    page_size = int(page_size)
    attend = functools.partial(_attention, axes=frozenset(), attn=c.attn,
                               blocks=(c.attn_block_q, c.attn_block_k))
    t = _mp_axis(set(mesh.axis_names)) if mesh is not None else None
    if t:
        heads = P(None, t, None, None)
        attend = _shard_map(attend, mesh=mesh, in_specs=(heads,) * 3,
                            out_specs=heads, check_vma=False)

    def prefill(params, cache, tokens, length, pages):
        _b, S = tokens.shape
        n_pages = S // page_size
        x = jnp.take(params["embed_weight"],
                     jnp.clip(tokens, 0, params["embed_weight"].shape[0] - 1),
                     axis=0)
        x = (x + params["pos_embed_weight"][:S]).astype(cdt)

        def layer(x, xs):
            lp, cl = xs
            h = _layernorm(x, lp["ln1_gamma"], lp["ln1_beta"])
            qkv = jnp.einsum("bsd,dthe->tbhse", h,
                             lp["attn_qkv_weight"].astype(cdt))
            q, k, v = qkv[0], qkv[1], qkv[2]
            # scatter K/V into this layer's pages: (1,H,S,Dh) → page grid
            kp = k[0].transpose(1, 0, 2).reshape(n_pages, page_size, -1)
            vp = v[0].transpose(1, 0, 2).reshape(n_pages, page_size, -1)
            with jax.named_scope("mx.gen.pool_write"):
                cl = cl.at[0, pages].set(kp.astype(cl.dtype))
                cl = cl.at[1, pages].set(vp.astype(cl.dtype))
            with jax.named_scope("mx.gen.attn"):
                o = attend(q, k, v)
            o = jnp.einsum("bhse,hed->bsd", o,
                           lp["attn_out_weight"].astype(cdt))
            return _ffn(x + o, lp, c, frozenset(), cdt), cl

        x, cache = lax.scan(layer, x, (_stacked_layer_params(params), cache))
        x = _layernorm(x, params["final_ln_gamma"], params["final_ln_beta"])
        x_last = lax.dynamic_index_in_dim(x[0], length - 1, axis=0,
                                          keepdims=False)
        logits = jnp.einsum("d,vd->v", x_last,
                            params["embed_weight"].astype(cdt))
        return cache, logits.astype(jnp.float32)

    return prefill


def _gather_pages(cl, block_tables, n_heads):
    """One layer's K and V of every slot, gathered from the layer's slice
    ``cl`` (2, P+1, page, H*Dh) of the pool in block-table order:
    (S, MP, page, H*Dh) → two (S, H, MP * page, Dh)."""
    S = block_tables.shape[0]
    kg, vg = (cl[kv][block_tables].reshape(S, -1, n_heads,
                                           cl.shape[-1] // n_heads)
              .transpose(0, 2, 1, 3) for kv in (0, 1))
    return kg, vg


def _decode_attend(config, block_k, mesh):
    """attend(q (S, H*Dh), cache, layer, block_tables, lengths) → (S, H*Dh)
    over columns ``< lengths[b]``, chosen from what the code can see: on a
    TPU the Pallas kernel over the pool in place (under ``shard_map`` over
    the heads an mp axis shards: a Mosaic kernel cannot be partitioned
    automatically); on the CPU test path the gathered pages through
    ``_paged_decode_attention``. ``kernel_platform()`` raises on any other
    backend."""
    c = config
    t = _mp_axis(set(mesh.axis_names)) if mesh is not None else None

    if kernel_platform() == "tpu":
        from ..kernels.paged_decode import paged_decode_attention

        attend = functools.partial(
            paged_decode_attention, block_k=block_k,
            n_heads=c.n_heads // (mesh.shape[t] if t else 1))
        if t:
            lanes = P(None, t)
            attend = _shard_map(
                attend, mesh=mesh,
                in_specs=(lanes, kv_cache_spec(mesh), P(), P(), P()),
                out_specs=lanes, check_vma=False)
        return attend

    def attend(q, cache, layer, block_tables, lengths):
        S = q.shape[0]
        with jax.named_scope("mx.gen.gather_kv"):
            kg, vg = _gather_pages(cache[layer], block_tables, c.n_heads)
        o = _paged_decode_attention(q.reshape(S, c.n_heads, 1, -1), kg, vg,
                                    lengths - 1, block_k)
        return o.reshape(S, -1)

    return attend


def make_decode_fn(config, slots, max_pages_per_slot, page_size,
                   block_k=None, mesh=None):
    """fn(params, cache, tokens (S,) int32, positions (S,) int32,
    block_tables (S, max_pages_per_slot) int32, active (S,) bool) →
    (cache', logits (S, V) fp32).

    One decode step for ``slots`` batch slots: embed token b at
    ``positions[b]``, write its per-layer K/V row in place at
    ``cache[layer, kv, block_tables[b, positions[b] // page_size],
    positions[b] % page_size]``, attend the slot's pages (columns <=
    position) where they lie in the pool, and emit next-token logits. The
    pool is a carry of the layer loop, never a scanned input: with the
    predictor's donation the program holds one pool and touches the rows
    it writes and the pages it reads. Inactive slots compute too (the
    batch shape is static) but their writes are routed to the scratch
    page, they attend nothing and their logits are zeroed. ``block_k``
    (key columns per online-softmax turn) defaults to the schedule-table
    consult at the decode shape (:func:`decode_schedule_shape`); ``mesh``
    is the replica group's mesh of a sharded bind (see
    :func:`_decode_attend` for what runs where)."""
    c = config
    cdt = jnp.dtype(c.dtype)
    page_size = int(page_size)
    max_ctx = int(max_pages_per_slot) * page_size
    if block_k is None:
        block_k = _decode_block_k(c, slots, max_ctx)
    attend = _decode_attend(c, block_k, mesh)

    def decode(params, cache, tokens, positions, block_tables, active):
        S = tokens.shape[0]
        emb = params["embed_weight"]
        x = jnp.take(emb, jnp.clip(tokens, 0, emb.shape[0] - 1), axis=0)
        pos = jnp.take(params["pos_embed_weight"],
                       jnp.clip(positions, 0,
                                params["pos_embed_weight"].shape[0] - 1),
                       axis=0)
        x = (x + pos).astype(cdt)[:, None, :]                # (S, 1, d)

        page_idx = positions // page_size
        offset = positions % page_size
        page = jnp.take_along_axis(block_tables, page_idx[:, None],
                                   axis=1)[:, 0]
        # inactive slots (and any unset table entry) write to scratch
        page = jnp.where(active, page, 0)
        lengths = jnp.where(active, positions + 1, 0)

        def layer(carry, xs):
            x, cache = carry
            lp, i = xs
            h = _layernorm(x, lp["ln1_gamma"], lp["ln1_beta"])
            qkv = jnp.einsum("bsd,dthe->tbhse", h,
                             lp["attn_qkv_weight"].astype(cdt))
            q, k, v = (a.reshape(S, -1) for a in qkv)  # (S, H*Dh), head-major
            with jax.named_scope("mx.gen.pool_write"):
                cache = cache.at[i, 0, page, offset].set(
                    k.astype(cache.dtype))
                cache = cache.at[i, 1, page, offset].set(
                    v.astype(cache.dtype))
            with jax.named_scope("mx.gen.attn"):
                o = attend(q.astype(cdt), cache, i, block_tables, lengths)
            o = jnp.einsum("bhse,hed->bsd",
                           o.reshape(S, c.n_heads, 1, -1),
                           lp["attn_out_weight"].astype(cdt))
            return (_ffn(x + o, lp, c, frozenset(), cdt), cache), None

        (x, cache), _ = lax.scan(
            layer, (x, cache),
            (_stacked_layer_params(params), jnp.arange(c.n_layers)))
        x = _layernorm(x, params["final_ln_gamma"], params["final_ln_beta"])
        logits = jnp.einsum("bsd,vd->bsv", x,
                            params["embed_weight"].astype(cdt))[:, 0]
        logits = jnp.where(active[:, None], logits, 0.0)
        return cache, logits.astype(jnp.float32)

    return decode


def make_extend_fn(config, slots, steps, max_pages_per_slot, page_size,
                   block_k=None):
    """fn(params, cache, tokens (S, T) int32, positions (S, T) int32,
    block_tables (S, max_pages_per_slot) int32, valid (S, T) bool) →
    (cache', logits (S, T, V) fp32) with ``S == slots``, ``T == steps``.

    The multi-token generalization of ``make_decode_fn`` (ISSUE 16):
    each slot appends up to T tokens against its already-cached pages in
    ONE compiled call. Token (b, t) is written at page
    ``block_tables[b, positions[b, t] // page_size]`` offset
    ``positions[b, t] % page_size``, then every row attends the slot's
    gathered pages under the per-row mask ``col <= positions[b, t]`` —
    all T writes of a layer land before that layer's gather, so row t
    sees rows < t of its own call (in-window causality is free). Two
    callers, same program shape:

    - shared-prefix tail prefill: S = 1, rows are the uncovered prompt
      tail at positions ``prefix_len..prompt_len-1`` — they attend the
      SHARED prefix pages but, because every row's position lies past
      the shared region, only ever write the request's private pages
      (the copy-on-write guarantee, asserted in tests);
    - speculative verify: rows are the pending token + k draft
      proposals; logits row t is the target model's next-token
      distribution after prefix+row t, so acceptance (argmax equality)
      reproduces the non-speculative greedy chain token-for-token.

    Invalid rows (valid == False: padded tails, slots speculating fewer
    than k tokens) write to the scratch page and return zero logits.
    Rows at positions past the verified prefix may leave REJECTED
    tokens' K/V behind — safe for the same reason padded prefill tails
    are: columns past a row's position are masked, and a later call
    writes the position before any row attends it."""
    c = config
    cdt = jnp.dtype(c.dtype)
    page_size = int(page_size)
    max_ctx = int(max_pages_per_slot) * page_size
    if block_k is None:
        block_k = _decode_block_k(c, slots, max_ctx)

    def extend(params, cache, tokens, positions, block_tables, valid):
        S, T = tokens.shape
        positions = jnp.maximum(positions, 0)
        emb = params["embed_weight"]
        x = jnp.take(emb, jnp.clip(tokens, 0, emb.shape[0] - 1), axis=0)
        pos_emb = jnp.take(
            params["pos_embed_weight"],
            jnp.clip(positions, 0, params["pos_embed_weight"].shape[0] - 1),
            axis=0)
        x = (x + pos_emb).astype(cdt)                        # (S, T, d)

        page_idx = jnp.clip(positions // page_size, 0, block_tables.shape[1] - 1)
        offset = positions % page_size
        page = jnp.take_along_axis(block_tables, page_idx, axis=1)  # (S, T)
        # invalid rows (and any unset table entry) write to scratch
        page = jnp.where(valid, page, 0)

        def layer(x, xs):
            lp, cl = xs
            h = _layernorm(x, lp["ln1_gamma"], lp["ln1_beta"])
            qkv = jnp.einsum("bsd,dthe->tbhse", h,
                             lp["attn_qkv_weight"].astype(cdt))
            q, k, v = qkv[0], qkv[1], qkv[2]          # (S, H, T, Dh)
            with jax.named_scope("mx.gen.pool_write"):
                cl = cl.at[0, page, offset].set(
                    k.transpose(0, 2, 1, 3).reshape(S, T, -1).astype(cl.dtype))
                cl = cl.at[1, page, offset].set(
                    v.transpose(0, 2, 1, 3).reshape(S, T, -1).astype(cl.dtype))
            with jax.named_scope("mx.gen.gather_kv"):
                kg, vg = _gather_pages(cl, block_tables, c.n_heads)
            with jax.named_scope("mx.gen.attn"):
                o = _paged_extend_attention(q.astype(cdt), kg, vg,
                                            positions, block_k)
            o = jnp.einsum("bhse,hed->bsd", o,
                           lp["attn_out_weight"].astype(cdt))
            return _ffn(x + o, lp, c, frozenset(), cdt), cl

        x, cache = lax.scan(layer, x, (_stacked_layer_params(params), cache))
        x = _layernorm(x, params["final_ln_gamma"], params["final_ln_beta"])
        logits = jnp.einsum("btd,vd->btv", x,
                            params["embed_weight"].astype(cdt))
        logits = jnp.where(valid[..., None], logits, 0.0)
        return cache, logits.astype(jnp.float32)

    return extend


def draft_from_layers(config, params, n_layers):
    """Self-draft for speculative decoding (ISSUE 16): slice the stacked
    layer params down to the FIRST ``n_layers`` transformer blocks,
    sharing the embedding / position / final-LN tensors with the target
    model. Returns ``(draft_config, draft_params)`` ready for a second
    :class:`~mxnet_tpu.serving.generate.GenerativePredictor` — no extra
    training, no extra checkpoint, and (because ``init_params`` stacks
    every per-layer tensor on a leading L axis) no copy of the shared
    tensors. A one-layer draft of an L-layer target is the cheap
    proposer whose agreement the verify step measures as
    ``acceptance_rate``."""
    n = int(n_layers)
    if not 1 <= n <= config.n_layers:
        raise ValueError(
            "draft_from_layers: n_layers must lie in [1, %d], got %d"
            % (config.n_layers, n))
    shared = ("embed_weight", "pos_embed_weight",
              "final_ln_gamma", "final_ln_beta")
    dparams = {k: (v if k in shared else v[:n]) for k, v in params.items()}
    return dataclasses.replace(config, n_layers=n), dparams


def kv_page_bytes(config, page_size):
    """Bytes one page of :func:`init_kv_cache`'s pool holds over all
    layers: keys and values of every head, in the compute type."""
    c = config
    return (c.n_layers * 2 * int(page_size) * c.n_heads
            * (c.d_model // c.n_heads) * jnp.dtype(c.dtype).itemsize)


def decode_counters(config):
    """Names of what the decode program counts on the device: none."""
    return ()
