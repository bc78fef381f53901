"""The latent-attention expert model (``models/mla_moe.py``) as Sarvam-105B
configures it, a full-rank query with q and k norms, DeepSeek's YaRN and no
indexer, against its plain reference (``benchmark/reference/sarvam_lm.py``),
and through ``GenerateServer``.  CPU, tiny widths, seeded float32 weights: the
one-shot forward, prefill then decode through the paged latent cache in the
blocked form and in the interpreted Pallas kernel at 64-row pages, the YaRN
frequencies and softmax scale at the published widths, each norm and YaRN, the
share of the experts with the shared expert once, the server with a pool
sized in bytes, and the precision controls.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import profiler
from mxnet_tpu.models import mla_moe as mm
from mxnet_tpu.serving import GenerateServer, GenerativePredictor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.reference import precision  # noqa: E402
from benchmark.reference import sarvam_lm as ref  # noqa: E402

YARN = dict(yarn_factor=40.0, yarn_original=4096)
TINY = dict(vocab=64, d_model=64, n_heads=4, n_layers=3, n_dense_layers=1, d_ff=96,
            d_expert=32, n_experts=16, experts_per_token=4, held_experts=(0, 1, 2, 3),
            route_scale=2.5, q_rank=0, kv_rank=16, d_nope=8, d_rope=8, d_v=8,
            indexer=False, qk_norm=True, rope_theta=1e4, norm_eps=1e-6,
            max_len=128, dtype="float32", **YARN)
# float32 program against a float32 reference, both at "highest": what is left
# is the order of the sums (blocks of keys, absorbed against expanded), a few
# float32 roundings of logits of size 5
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


@pytest.mark.parametrize("layers", [1, 3])
def test_forward_matches_the_reference(layers):
    """The dense layer alone and the whole model, one-shot, on logits."""
    cfg, params, m = build(n_layers=layers)
    tok = tokens(40)
    want = np.asarray(ref.logits(params, jnp.asarray(tok), m))
    got = np.asarray(mm.make_forward_fn(cfg)(params, jnp.asarray(tok)))
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < TOL


def test_the_layout_has_a_full_rank_query_and_no_indexer():
    cfg, params, _m = build()
    assert params["q_weight"].shape == (3, 64, 4, 16)
    assert params["q_norm"].shape == (3, 16) and params["k_norm"].shape == (3, 8)
    assert not [k for k in params if k.startswith(("q_a_", "q_b_", "index_"))]
    cache = mm.init_kv_cache(cfg, 10, 4)
    assert list(cache) == ["latent"] and len(cache["latent"]) == 3
    assert mm.kv_page_bytes(cfg, 4) == 3 * 4 * 128 * 4
    assert mm.decode_counters(cfg)[-1] == "attn_rows_read"


def test_yarn_pins_at_the_published_widths():
    """DeepSeek YaRN at rope dim 64, base 1e4, factor 40, original 4096, beta
    32 / 1: the correction range is pairs 10 to 23 (pairs up to 10 keep
    ``base^(-2i/64)``, pairs from 23 on are that over 40, a ramp between),
    and the softmax scale is ``192^-1/2 (0.1 ln 40 + 1)^2``."""
    cfg = mm.LatentMoEConfig(**dict(TINY, d_nope=128, d_rope=64, rope_theta=1e4))
    freq = mm._yarn_freq(cfg, 64).astype(np.float64)
    plain = 1e4 ** (-np.arange(0, 64, 2) / 64)
    ratio = freq / plain
    assert np.allclose(ratio[:11], 1.0, rtol=1e-6) and ratio[11] < 1.0
    assert np.allclose(ratio[23:], 1 / 40, rtol=1e-6) and ratio[22] > 1 / 40
    ramp = (np.arange(11, 23) - 10) / 13
    assert np.allclose(ratio[11:23], (1 - ramp) + ramp / 40, rtol=1e-6)
    assert np.allclose(ref.yarn_inv_freq(dict(TINY, rope_theta=1e4), 64), freq, rtol=1e-6)
    assert mm._softmax_scale(cfg) == pytest.approx(0.135234, abs=5e-7)
    assert mm._yarn_mscale(40.0) == pytest.approx(1.368888, abs=5e-7)
    plain_cfg = mm.LatentMoEConfig(**dict(TINY, yarn_factor=0.0))
    assert mm._softmax_scale(plain_cfg) == 16 ** -0.5


# what feeds each norm: the query's projection, the rotary key's columns
FEEDS = {"q_norm": lambda p, c: {"q_weight": p["q_weight"] * 3},
         "k_norm": lambda p, c: {"kv_a_weight": p["kv_a_weight"].at[..., c.kv_rank:].multiply(3)}}


@pytest.mark.parametrize("switch", ["q_norm", "k_norm", "yarn_factor"])
def test_each_norm_and_yarn_are_in_the_mathematics(switch):
    """Program and reference agree on every variant, and each piece is
    applied.  YaRN: off moves the logits.  A norm (both sit under the one
    ``qk_norm`` switch): its gain drawn away from 1 moves them, and what feeds
    it scaled threefold leaves them as they were while ``qk_norm`` is on and
    moves them once it is off."""
    cfg, params, m = build()
    tok = jnp.asarray(tokens(48))

    def logits(p, cfg=cfg, m=m):
        want = np.asarray(ref.logits(p, tok, m))
        got = np.asarray(mm.make_forward_fn(cfg)(p, tok))
        assert np.abs(got - want).max() < TOL
        return want

    def moved(a, b):
        return np.abs(a - b).max() > 100 * TOL

    base = logits(params)
    if switch == "yarn_factor":
        plain_cfg, _params, plain_m = build(yarn_factor=0.0)
        assert moved(logits(params, plain_cfg, plain_m), base)
        return
    gain = 1 + 0.3 * np.random.RandomState(5).randn(*params[switch].shape)
    assert moved(logits(dict(params, **{switch: jnp.asarray(gain, jnp.float32)})), base)
    fed = dict(params, **FEEDS[switch](params, cfg))
    assert not moved(logits(fed), base)
    off_cfg, _params, off_m = build(qk_norm=False)

    def unnormed(p):
        return {k: v for k, v in p.items() if k not in ("q_norm", "k_norm")}
    assert moved(logits(unnormed(fed), off_cfg, off_m), logits(unnormed(params), off_cfg, off_m))


def _serve_by_hand(cfg, params, tok, n_prompt, page=4, per_slot=16, bucket=32, block_k=8):
    """Prefill ``n_prompt`` tokens, then decode the rest one at a time in
    slot 1 of 2 (slot 0 idle); logits of every position from n_prompt - 1."""
    cache = mm.init_kv_cache(cfg, per_slot + 4, page)
    prefill = jax.jit(mm.make_prefill_fn(cfg, page))
    decode = jax.jit(mm.make_decode_fn(cfg, 2, per_slot, page, block_k=block_k))
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


@pytest.mark.parametrize("n_prompt,block_k", [(5, 8), (21, 4), (9, 64)])
def test_prefill_then_decode_matches_the_reference_full_forward(n_prompt, block_k):
    """Through the paged latent cache in the blocked form: the prompt in the
    expanded form, every later token in the absorbed one over all cached
    rows, in key blocks smaller than, equal to and larger than the cache."""
    cfg, params, m = build()
    tok = tokens(40)
    want = np.asarray(ref.logits(params, jnp.asarray(tok), m))
    got, counts = _serve_by_hand(cfg, params, tok, n_prompt, block_k=block_k)
    assert np.abs(got - want[n_prompt - 1:]).max() < TOL
    experts = cfg.n_layers - cfg.n_dense_layers
    for t, c in zip(range(n_prompt, len(tok)), counts):
        assert c["attn_rows_read"] == cfg.n_layers * (t + 1)
        assert c["moe_tokens"] == experts                 # one active slot
        assert c["moe_pairs_held"] <= cfg.experts_per_token * experts
        assert c["moe_experts_touched"] <= c["moe_pairs_held"] <= c["moe_pairs_at_max_load"]


@pytest.mark.parametrize("n_prompt", [5, 21])
def test_served_prefill_and_eight_decode_steps_match_the_forward(n_prompt):
    """Through the predictor, whose bind transposes the leaves of
    ``served_leaves``: prefill and 8 decode steps (full-rank query and every cached row attended in the
    blocked form) give the one-shot
    forward's logits on the leaves as drawn."""
    cfg, params, _m = build(seed=7)
    tok = tokens(n_prompt + 8, seed=4)
    want = np.asarray(mm.make_forward_fn(cfg)(params, jnp.asarray(tok)))
    pred = GenerativePredictor(cfg, params, slots=2, page_size=4, max_ctx=64)
    assert sorted(mm.served_leaves(cfg)) == ["kv_b_weight"]
    assert pred.pool_stats()["params_transposed"] == len(mm.served_leaves(cfg))
    pages = pred.pool.alloc(pred.pages_needed(n_prompt + 8))
    got = [pred.prefill(tok[:n_prompt], pages[:pred.pages_needed(n_prompt)])]
    table = np.zeros((2, pred.max_pages_per_slot), np.int32)
    table[1, :len(pages)] = pages
    for t in range(n_prompt, n_prompt + 8):
        got.append(pred.decode(np.array([0, tok[t]], np.int32), np.array([0, t], np.int32),
                               table, np.array([False, True]))[1])
    assert np.abs(want).max() > 0.5
    assert np.abs(np.stack(got) - want[n_prompt - 1:]).max() < TOL


def test_prefill_then_decode_through_the_interpreted_kernel_at_64_row_pages(monkeypatch):
    """The same through ``mx_mla_paged_decode`` (interpreted on the CPU) with
    the cell's page of 64 rows and a block table of 1040 pages a slot, the
    cell's 1072 in scale: the table is a scalar-prefetch operand."""
    import mxnet_tpu.kernels.mla_paged_decode  # noqa: F401

    monkeypatch.setattr(mm, "kernel_platform", lambda: "tpu")
    cfg, params, m = build(max_len=1040 * 64)
    tok = tokens(150)
    want = np.asarray(ref.logits(params, jnp.asarray(tok), m))
    got, counts = _serve_by_hand(cfg, params, tok, 70, page=64, per_slot=1040,
                                 bucket=128, block_k=128)
    assert np.abs(got - want[69:]).max() < TOL
    assert counts[-1]["attn_rows_read"] == cfg.n_layers * 150


def test_kernel_reads_a_slot_past_its_thousandth_page():
    """The interpreted kernel against ``blocked_attention`` at 64-row pages,
    one slot 1001 pages long in a shuffled 1040-page table, one short and
    one empty."""
    from mxnet_tpu.kernels.mla_paged_decode import mla_paged_decode_attention

    rng = np.random.RandomState(0)
    S, H, W, page, P = 3, 4, 128, 64, 1100
    pool = jnp.asarray(rng.randn(P + 1, page, W).astype(np.float32))
    q = jnp.asarray(rng.randn(S, H, W).astype(np.float32) * 0.3)
    table = np.zeros((S, 1040), np.int32)
    table[0] = rng.permutation(np.arange(1, P + 1))[:1040]
    table[1, :3] = [P, 5, 9]
    lengths = jnp.asarray([1000 * page + 7, 150, 0], jnp.int32)
    want = np.asarray(mm.blocked_attention(q, pool, jnp.asarray(table), lengths, 64, 0.2,
                                           512))
    got = np.asarray(mla_paged_decode_attention(q, pool, jnp.asarray(table), lengths,
                                                d_value=64, scale=0.2, block_k=512,
                                                interpret=True))
    assert np.abs(want[:2]).max() > 0.05 and not got[2].any()
    assert np.abs(got - want).max() < 1e-5


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts of all 16 shares (one of the 16
    experts each), with the shared expert counted once, are the reference's
    uncut expert layer."""
    cfg, params, m = build(n_layers=1, n_dense_layers=0, held_experts=tuple(range(16)))
    h = jnp.asarray(np.random.RandomState(1).randn(24, 64).astype(np.float32))
    lp_full = ref.layer_leaves(params, m, 0)
    uncut = np.asarray(ref.experts(h, lp_full, m, lambda x: x))
    shared = np.asarray(ref.swiglu(h, lp_full["shared_gate_weight"],
                                   lp_full["shared_up_weight"],
                                   lp_full["shared_down_weight"], lambda x: x))
    total, pairs = shared.copy(), 0
    every = jnp.ones((24,), bool)
    for rank in range(16):
        share = mm.LatentMoEConfig(**dict(m, held_experts=(rank,)))
        cut = dict(params)
        for k in ("expert_gate_weight", "expert_up_weight", "expert_down_weight"):
            cut[k] = params[k][:, rank:rank + 1]
        y, counts = mm._moe(h, mm._layer(cut, 0, share), share, jnp.float32, every)
        total += np.asarray(y) - shared
        pairs += int(counts["moe_pairs_held"])
    assert pairs == 24 * cfg.experts_per_token       # every pair on one share
    assert np.abs(uncut).max() > 0.1
    assert np.abs(total - uncut).max() < TOL


def _gaps(params, m, prompt, served):
    seq = np.concatenate([prompt, np.asarray(served, np.int32)])[:-1]
    logits = np.asarray(ref.logits(params, jnp.asarray(seq), m))[len(prompt) - 1:]
    return logits.max(axis=-1) - logits[np.arange(len(served)), served]


def test_generate_server_serves_it_under_page_growth_and_slot_reuse():
    """Five requests on two slots through ``submit``, the pool sized in bytes
    to what two streams hold (not slots x max_ctx): slots and pages are
    reused, prompts cross page boundaries and every stream grows new pages
    while decoding.  Every served token is the reference's first choice (to
    rounding), the pool is left empty, and the device counters add up."""
    cfg, params, m = build()
    prompts = [tokens(n, seed=10 + n) for n in (5, 13, 21, 9, 30)]
    streamed = [[] for _ in prompts]
    page_bytes = mm.kv_page_bytes(cfg, 4)
    with GenerateServer(cfg, params, slots=2, page_size=4, max_ctx=64, max_steps=20,
                        pool_bytes=24 * page_bytes, stream_flush=1, name="tsarvam") as srv:
        pred = srv.predictor
        assert pred.page_bytes == page_bytes and pred.pool.num_pages == 24 < 2 * 16
        assert list(pred._kv) == ["latent"] and len(pred._kv["latent"]) == cfg.n_layers
        assert pred.block_k == 64
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
    assert st["moe_tokens"] == (cfg.n_layers - cfg.n_dense_layers) * st["active_slot_steps"]
    assert st["attn_rows_read"] == sum(
        cfg.n_layers * (len(p) + j + 1) for i, p in enumerate(prompts)
        for j in range(12 + i - 1))
    assert 0 < st["moe_pairs_held"] <= st["moe_pairs_at_max_load"]
    assert st["moe_experts_touched"] <= st["moe_pairs_held"]
    assert st["dsa_keys_scanned"] == 0


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
