"""The latent-attention expert model (``models/mla_moe.py``) as Hy4-preview
configures it: four hyper-connected streams, an indexer on the ``full``
layers whose selection the ``shared`` layers reuse, a gated attention with
learned sinks, a clamped SwiGLU and a float32 head, against its plain
reference (``benchmark/reference/hy4_lm.py``), and through
``GenerateServer``.  CPU, tiny widths, seeded float32 weights: the one-shot
forward, prefill then decode through the paged cache with contexts past
``index_topk``, Sinkhorn's doubly stochastic ``H_res``, each piece of the
mathematics against its absence, the layout of the index pools, the share of
the experts with the shared expert once, the server with a pool sized in
bytes, and the precision controls.
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import profiler
from mxnet_tpu.models import mla_moe as mm
from mxnet_tpu.serving import GenerateServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.reference import hy4_lm as ref  # noqa: E402
from benchmark.reference import precision  # noqa: E402

TYPES = ("full", "full", "shared", "shared", "shared")
TINY = dict(vocab=64, d_model=32, n_heads=4, n_layers=5, n_dense_layers=1, d_ff=48,
            d_expert=16, n_experts=16, experts_per_token=4, held_experts=(0, 1, 2, 3),
            route_scale=2.827, q_rank=24, kv_rank=16, d_nope=8, d_rope=8, d_v=8,
            index_heads=4, index_dim=16, index_rope_dim=8, index_topk=8,
            indexer_types=TYPES, hc_mult=4, hc_magnitude=2.0, hc_eps=1e-6,
            attn_gate=True, attn_sink=True, swiglu_limit=10.0,
            head_fp32=True, rope_theta=1e7, norm_eps=1e-5, index_norm_eps=1e-6,
            max_len=256, dtype="float32")
# float32 program against a float32 reference, both at "highest": what is left
# is the order of the sums (blocks of keys, absorbed against expanded, the
# hyper-connections' projection over the rms), a few float32 roundings of
# logits of size 3
TOL = 2e-5


def build(seed=3, **over):
    fields = dict(TINY, **over)
    cfg = mm.LatentMoEConfig(**fields)
    return cfg, mm.init_params(cfg, seed=seed, scale=0.15, bias_scale=0.05), fields


def tokens(n, seed=0, vocab=64):
    return np.random.RandomState(seed).randint(0, vocab, n).astype(np.int32)


@pytest.fixture(autouse=True)
def _exact():
    profiler.generate_reset()
    with jax.default_matmul_precision("highest"):
        yield


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, page=4, slots=2, per_slot=16):
    """The jitted forward, prefill and decode of a configuration, compiled
    once for the tests that share it."""
    cfg = mm.LatentMoEConfig(**dict(cfg_items))
    return (mm.make_forward_fn(cfg), jax.jit(mm.make_prefill_fn(cfg, page)),
            jax.jit(mm.make_decode_fn(cfg, slots, per_slot, page)))


def programs(cfg):
    return _programs(tuple(sorted(vars(cfg).items())))


def forward(cfg, params, tok):
    return np.asarray(programs(cfg)[0](params, jnp.asarray(tok)))


@pytest.mark.parametrize("layers", [2, 5])
def test_forward_matches_the_reference(layers):
    """The dense layer and the first expert layer (both full), and the whole
    period with its three shared layers, one-shot, on logits."""
    cfg, params, m = build(n_layers=layers, indexer_types=TYPES[:layers])
    tok = tokens(40)
    want = np.asarray(ref.logits(params, jnp.asarray(tok), m))
    assert np.abs(want).max() > 0.5
    assert np.abs(forward(cfg, params, tok) - want).max() < TOL


def test_the_layout_has_index_pools_for_full_layers_alone():
    cfg, params, _m = build()
    for k in ("index_q_weight", "index_k_weight", "index_w_weight"):
        assert params[k].shape[0] == 2
    assert params["hc_attn_proj"].shape == (5, 4 * 32, 24)
    assert params["hc_ffn_scale"].shape == (5, 3)
    assert params["o_gate_weight"].shape == (5, 32, 4 * 8)
    assert params["attn_sink"].shape == (5, 4)
    cache = mm.init_kv_cache(cfg, 10, 4)
    assert len(cache["latent"]) == 5 and len(cache["index"]) == 2
    # 4 rows of five 128-lane latent rows and two 16-wide index keys, float32
    assert mm.kv_page_bytes(cfg, 4) == 4 * (5 * 128 + 2 * 16) * 4
    assert mm.decode_counters(cfg)[-1] == "dsa_selections_reused"
    every = mm.LatentMoEConfig(**dict(TINY, indexer_types=()))
    assert "dsa_selections_reused" not in mm.decode_counters(every)
    with pytest.raises(ValueError):
        mm.LatentMoEConfig(**dict(TINY, indexer_types=("shared",) + TYPES[1:]))


@pytest.mark.parametrize("iters,rows_within", [(20, 1e-3), (50, 1e-5)])
def test_sinkhorn_makes_h_res_doubly_stochastic(iters, rows_within, monkeypatch):
    """Each turn ends on the columns, so they sum to 1 within float32
    rounding; the rows converge: at the program's 20 turns the slowest of
    these 128 maps is 7e-4 off (entries of exp(normal(0, 1.2)) are skewed),
    at 50 all are within 1e-5."""
    monkeypatch.setattr(mm, "HC_SINKHORN_ITERS", iters)
    cfg, params, _m = build()
    lp = mm._layer(params, 2, cfg)
    x = jnp.asarray(np.random.RandomState(4).randn(64, 4, 32).astype(np.float32))
    for half in ("attn", "ffn"):
        _u, (post, res) = mm._hc_in(x, lp, half, cfg, jnp.float32)
        res = np.asarray(res)
        assert np.abs(res.sum(axis=-2) - 1).max() < 1e-5
        assert np.abs(res.sum(axis=-1) - 1).max() < rows_within
        assert res.min() > 0 and np.asarray(post).max() < 2.0


def _transposed_res(hc_out):
    return lambda x, y, maps, cdt: hc_out(
        x, y, None if maps is None else (maps[0], jnp.swapaxes(maps[1], 1, 2)), cdt)


# what each fault leaves out of the program; the reference keeps all of it
FAULTS = {
    "h_res_transposed": ({}, lambda mp: mp.setattr(mm, "_hc_out", _transposed_res(mm._hc_out))),
    "h_post_without_its_2": ({"hc_magnitude": 1.0}, None),
    "no_sink": ({"attn_sink": False}, None),
    "no_gate": ({"attn_gate": False}, None),
    "no_clamp": ({"swiglu_limit": 0.0}, None),
    "shared_layers_choose_their_own_keys": ({"indexer_types": ("full",) * 5}, None),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_piece_is_in_the_mathematics(fault, monkeypatch):
    """The program matches the reference, and with the piece left out (or,
    for the transposed H_res, put in wrongly) it is off by far more than the
    tolerance.  The clamp is judged on inputs past 10: the FFNs' gate and up
    projections scaled fivefold in program and reference alike."""
    cfg, params, m = build()
    tok = tokens(40)
    if fault == "no_clamp":
        params = dict(params, **{k: params[k] * 5 for k in params
                                 if k.endswith(("gate_weight", "up_weight"))
                                 and k != "o_gate_weight"})
    want = np.asarray(ref.logits(params, jnp.asarray(tok), m))
    assert np.abs(forward(cfg, params, tok) - want).max() < TOL
    over, patch = FAULTS[fault]
    wrong = mm.LatentMoEConfig(**dict(TINY, **over))
    served = {k: v for k, v in params.items() if k in mm.param_shapes(wrong)}
    if fault == "shared_layers_choose_their_own_keys":
        # each shared layer an indexer of its own, drawn as the full ones are
        own = mm.init_params(wrong, seed=9, scale=0.15)
        served = dict(served, **{k: own[k].at[:2].set(params[k])
                                 for k in params if k.startswith("index_")})
    if patch:
        # a program traced anew under the patch, not the one compiled before
        patch(monkeypatch)
        got = np.asarray(mm.make_forward_fn(wrong)(served, jnp.asarray(tok)))
    else:
        got = forward(wrong, served, tok)
    assert np.abs(got - want).max() > 100 * TOL, fault


def _serve_by_hand(cfg, params, tok, n_prompt, page=4, per_slot=16, bucket=32):
    """Prefill ``n_prompt`` tokens, then decode the rest one at a time in
    slot 1 of 2 (slot 0 idle); logits of every position from n_prompt - 1."""
    cache = mm.init_kv_cache(cfg, per_slot + 4, page)
    _forward, prefill, decode = programs(cfg)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n_prompt] = tok[:n_prompt]
    pages = np.zeros((bucket // page,), np.int32)
    used = -(-n_prompt // page)
    # pages in a scattered order, from the far end of the pool
    order = np.random.RandomState(7).permutation(np.arange(1, per_slot + 5))
    pages[:used] = order[:used]
    cache, first = prefill(params, cache, padded, np.int32(n_prompt), pages)
    out, counts = [np.asarray(first)], []
    table = np.zeros((2, per_slot), np.int32)
    table[1, :-(-len(tok) // page)] = order[:-(-len(tok) // page)]
    for t in range(n_prompt, len(tok)):
        cache, (logits, count) = decode(
            params, cache, np.array([0, tok[t]], np.int32), np.array([0, t], np.int32),
            table, np.array([False, True]))
        assert not np.asarray(logits)[0].any()          # the idle slot
        out.append(np.asarray(logits)[1])
        counts.append(dict(zip(mm.decode_counters(cfg), np.asarray(count).tolist())))
    return np.stack(out), counts


@pytest.mark.parametrize("n_prompt", [5, 21])
def test_prefill_then_decode_matches_the_reference_full_forward(n_prompt):
    """Through the paged cache: the prompt in the expanded form (the shared
    layers through the full layer's packed mask), every later token in the
    absorbed one (the shared layers at the full layer's kept ids), with
    contexts to 40 past ``index_topk`` 8, so the selection and its reuse
    decide."""
    cfg, params, m = build()
    tok = tokens(40)
    want = np.asarray(ref.logits(params, jnp.asarray(tok), m))
    got, counts = _serve_by_hand(cfg, params, tok, n_prompt)
    assert np.abs(got - want[n_prompt - 1:]).max() < TOL
    for t, c in zip(range(n_prompt, len(tok)), counts):
        assert c["dsa_keys_scanned"] == 2 * (t + 1)            # the full layers
        assert c["dsa_keys_selected"] == 5 * min(t + 1, cfg.index_topk)
        assert c["dsa_selections_reused"] == 3
        assert c["moe_tokens"] == 4


def test_thirty_two_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts of all 32 shares (one of 32 experts
    each), with the shared expert counted once, are the reference's uncut
    expert sublayer; the clamp is in both (inputs past 10)."""
    cfg, params, m = build(n_layers=2, indexer_types=TYPES[:2], n_experts=32,
                           held_experts=tuple(range(32)))
    params = dict(params, **{k: params[k] * 5 for k in params
                             if k.startswith(("expert_", "shared_")) and "down" not in k})
    h = jnp.asarray(np.random.RandomState(1).randn(24, 32).astype(np.float32))
    lp_full = ref.layer_leaves(params, m, 1)
    uncut = np.asarray(ref.experts(h, lp_full, m, lambda x: x))
    shared = np.asarray(ref.swiglu(h, lp_full["shared_gate_weight"],
                                   lp_full["shared_up_weight"],
                                   lp_full["shared_down_weight"], m, lambda x: x))
    total, pairs = shared.copy(), 0
    every = jnp.ones((24,), bool)
    for rank in range(32):
        share = mm.LatentMoEConfig(**dict(m, held_experts=(rank,)))
        cut = dict(params)
        for k in ("expert_gate_weight", "expert_up_weight", "expert_down_weight"):
            cut[k] = params[k][:, rank:rank + 1]
        y, counts = mm._moe(h, mm._layer(cut, 1, share), share, jnp.float32, every)
        total += np.asarray(y) - shared
        pairs += int(counts["moe_pairs_held"])
    assert pairs == 24 * cfg.experts_per_token       # every pair on one share
    assert np.abs(uncut).max() > 0.1
    assert np.abs(total - uncut).max() < TOL


def _gaps(params, m, prompt, served, length=64):
    """The reference over the sequence padded to ``length`` (causal: the
    padding moves no earlier logit, and one length is one program)."""
    seq = np.concatenate([prompt, np.asarray(served, np.int32)])[:-1]
    padded = np.zeros((length,), np.int32)
    padded[:len(seq)] = seq
    logits = np.asarray(ref.logits(params, jnp.asarray(padded), m))
    logits = logits[len(prompt) - 1:len(seq)]
    return logits.max(axis=-1) - logits[np.arange(len(served)), served]


def test_generate_server_serves_it_under_page_growth_and_slot_reuse():
    """Five requests on two slots through ``submit``, the pool sized in bytes
    to what two streams hold (not slots x max_ctx): slots and pages are
    reused, prompts cross page boundaries and every stream grows new pages
    while decoding past ``index_topk``.  Every served token is the
    reference's first choice (to rounding), the pool is left empty, and the
    device counters add up."""
    cfg, params, m = build()
    prompts = [tokens(n, seed=10 + n) for n in (5, 13, 21, 9, 30)]
    streamed = [[] for _ in prompts]
    page_bytes = mm.kv_page_bytes(cfg, 4)
    with GenerateServer(cfg, params, slots=2, page_size=4, max_ctx=64, max_steps=20,
                        pool_bytes=24 * page_bytes, stream_flush=1, name="thy4") as srv:
        pred = srv.predictor
        assert pred.page_bytes == page_bytes and pred.pool.num_pages == 24 < 2 * 16
        assert len(pred._kv["latent"]) == 5 and len(pred._kv["index"]) == 2
        futures = [srv.submit(p, max_new_tokens=12 + i, stream_fn=streamed[i].extend)
                   for i, p in enumerate(prompts)]
        results = [f.result(timeout=120) for f in futures]
        stats = pred.pool_stats()
    assert stats["in_use"] == 0 and stats["allocs"] == stats["frees"]
    assert stats["allocs"] > sum(-(-len(p) // 4) for p in prompts)     # growth
    for i, (p, r) in enumerate(zip(prompts, results)):
        assert r["finish_reason"] == "length" and len(r["tokens"]) == 12 + i
        assert streamed[i] == r["tokens"]
        assert _gaps(params, m, p, r["tokens"]).max() < 1e-4
    st = profiler.generate_stats()
    assert st["dsa_selections_reused"] == 3 * st["active_slot_steps"]
    steps = [len(p) + j + 1 for i, p in enumerate(prompts) for j in range(12 + i - 1)]
    assert st["dsa_keys_scanned"] == 2 * sum(steps)
    assert st["dsa_keys_selected"] == 5 * sum(min(n, cfg.index_topk) for n in steps)
    assert st["moe_tokens"] == 4 * st["active_slot_steps"]
    assert st["attn_rows_read"] == 0


@pytest.mark.parametrize("control", ["bf16", "int8"])
def test_a_lower_precision_breaks_the_tolerance(control):
    """The control: the reference with every product's operands rounded to
    bfloat16 (the precision below this test's float32) or to int8 (the one
    below the cell's bfloat16) is further from the exact reference than the
    program may be, so computing in it cannot pass."""
    _cfg, params, m = build()
    tok = jnp.asarray(tokens(40))
    want = np.asarray(ref.logits(params, tok, m))
    low = np.asarray(ref.logits(params, tok, m, precision.CONTROLS[control]))
    assert np.abs(low - want).max() > 100 * TOL
