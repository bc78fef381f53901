"""ISSUE 36 — the latent-attention expert model (``models/mla_moe.py``)
against its plain reference (``benchmark/reference/glm5_lm.py``), and through
``GenerateServer``.  CPU, tiny widths, seeded float32 weights: each layer kind
and the whole model, prefill then decode through the two caches, the absorbed
form against the expanded one, contexts shorter and longer than a tiny
``index_topk``, the share of the experts and of the vocabulary, the counters,
and that none of it reaches the transformer's programs.
"""
import hashlib
import os
import subprocess
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
from benchmark.reference import glm5_lm as ref  # noqa: E402

TINY = dict(vocab=64, d_model=64, n_heads=4, n_layers=3, n_dense_layers=1, d_ff=96,
            d_expert=32, n_experts=32, experts_per_token=4, held_experts=(0, 1),
            route_scale=2.5, q_rank=32, kv_rank=16, d_nope=8, d_rope=8, d_v=16,
            index_heads=4, index_dim=16, index_rope_dim=8, index_topk=8,
            rope_theta=1e4, norm_eps=1e-5, index_norm_eps=1e-6, max_len=128,
            dtype="float32")
KINDS = {"whole": {}, "dense_layer": dict(n_layers=1, n_dense_layers=1),
         "expert_layer": dict(n_layers=1, n_dense_layers=0)}
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


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_forward_matches_the_reference(kind):
    """Each layer kind alone and the whole model, one-shot, on logits; 40
    positions, so that both "all keys" (under 8) and selection are met."""
    cfg, params, m = build(**KINDS[kind])
    tok = tokens(40)
    want = np.asarray(ref.logits(params, jnp.asarray(tok), m))
    got = np.asarray(mm.make_forward_fn(cfg)(params, jnp.asarray(tok)))
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < TOL


def _serve_by_hand(cfg, params, tok, n_prompt, page=4, bucket=32):
    """Prefill ``n_prompt`` tokens, then decode the rest one at a time in
    slot 1 of 2 (slot 0 idle); logits of every position from n_prompt - 1."""
    cache = mm.init_kv_cache(cfg, 20, page)
    prefill = jax.jit(mm.make_prefill_fn(cfg, page))
    decode = jax.jit(mm.make_decode_fn(cfg, 2, 16, page))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n_prompt] = tok[:n_prompt]
    pages = np.zeros((bucket // page,), np.int32)
    used = -(-n_prompt // page)
    pages[:used] = np.arange(1, used + 1)
    cache, first = prefill(params, cache, padded, np.int32(n_prompt), pages)
    out, counts = [np.asarray(first)], []
    table = np.zeros((2, 16), np.int32)
    table[1, :-(-len(tok) // page)] = np.arange(1, -(-len(tok) // page) + 1)
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
    """Through the latent and the index cache: the prompt in the expanded
    form, every later token in the absorbed one over the selected rows.  A
    5-token prompt starts under ``index_topk`` 8 (all keys) and decodes past
    it; a 21-token one selects from the first step."""
    cfg, params, m = build()
    tok = tokens(40)
    want = np.asarray(ref.logits(params, jnp.asarray(tok), m))
    got, counts = _serve_by_hand(cfg, params, tok, n_prompt)
    assert np.abs(got - want[n_prompt - 1:]).max() < TOL
    for t, c in zip(range(n_prompt, len(tok)), counts):
        assert c["dsa_keys_scanned"] == cfg.n_layers * (t + 1)
        assert c["dsa_keys_selected"] == cfg.n_layers * min(t + 1, cfg.index_topk)
        assert c["moe_tokens"] == 2          # one active slot, two expert layers
        assert c["moe_experts_touched"] <= c["moe_pairs_held"] <= 2 * 2
        assert c["moe_pairs_held"] <= c["moe_pairs_at_max_load"] <= 2 * c["moe_pairs_held"]


def test_absorbed_decode_matches_expanded_forward():
    cfg, params, _m = build(seed=5)
    tok = tokens(36, seed=2)
    expanded = np.asarray(mm.make_forward_fn(cfg)(params, jnp.asarray(tok)))
    absorbed, _ = _serve_by_hand(cfg, params, tok, 9)
    assert np.abs(absorbed - expanded[8:]).max() < TOL


def test_equal_index_scores_keep_the_earlier_position():
    """The mask the prefill builds without a sort is the set ``lax.top_k``
    returns, ties and rows with fewer than k entries included."""
    rng = np.random.RandomState(0)
    scores = rng.randint(-2, 3, (6, 24)).astype(np.float32)     # many ties
    scores[4, 5:] = -np.inf                                     # 5 finite < k
    scores[5] = 0.0
    for k in (1, 8, 24):
        want = np.zeros(scores.shape, bool)
        np.put_along_axis(want, np.asarray(jax.lax.top_k(scores, k)[1]), True, axis=1)
        got = np.asarray(mm._largest_k(jnp.asarray(scores), k))
        finite = np.isfinite(scores)
        assert (got & finite == want & finite).all(), k


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts of all 16 shares, with the shared
    expert counted once, are the reference's uncut expert layer."""
    cfg, params, m = build(n_layers=1, n_dense_layers=0,
                           held_experts=tuple(range(32)))
    h = jnp.asarray(np.random.RandomState(1).randn(24, 64).astype(np.float32))
    lp_full = ref.layer_leaves(params, 0, m)
    uncut = np.asarray(ref.experts(h, lp_full, m, lambda x: x))
    shared = np.asarray(ref.swiglu(h, lp_full["shared_gate_weight"],
                                   lp_full["shared_up_weight"],
                                   lp_full["shared_down_weight"], lambda x: x))
    total, pairs = shared.copy(), 0
    every = jnp.ones((24,), bool)
    for rank in range(16):
        held = (2 * rank, 2 * rank + 1)
        share = mm.LatentMoEConfig(**dict(m, held_experts=held))
        cut = dict(params)
        for k in ("expert_gate_weight", "expert_up_weight", "expert_down_weight"):
            cut[k] = params[k][:, 2 * rank:2 * rank + 2]
        y, counts = mm._moe(h, mm._layer(cut, 0, share), share, jnp.float32, every)
        total += np.asarray(y) - shared
        pairs += int(counts["moe_pairs_held"])
    assert pairs == 24 * cfg.experts_per_token       # every pair on one share
    assert np.abs(uncut).max() > 0.1
    assert np.abs(total - uncut).max() < TOL


def test_vocabulary_slice_is_the_slice_of_the_logits():
    cfg, params, _m = build(vocab=128)
    cut_cfg = mm.LatentMoEConfig(**dict(TINY, vocab=32))
    cut = dict(params, embed_weight=params["embed_weight"][:32],
               head_weight=params["head_weight"][:32])
    tok = jnp.asarray(tokens(20, vocab=32))
    whole = np.asarray(mm.make_forward_fn(cfg)(params, tok))
    part = np.asarray(mm.make_forward_fn(cut_cfg)(cut, tok))
    assert part.shape == (20, 32)
    assert np.abs(part - whole[:, :32]).max() < TOL


def _gaps(params, m, prompt, served):
    seq = np.concatenate([prompt, np.asarray(served, np.int32)])[:-1]
    logits = np.asarray(ref.logits(params, jnp.asarray(seq), m))[len(prompt) - 1:]
    return logits.max(axis=-1) - logits[np.arange(len(served)), served]


def test_generate_server_serves_it_under_page_growth_and_slot_reuse():
    """Five requests on two slots through ``submit``: slots and pages are
    reused, prompts cross page boundaries and every stream grows new pages
    while decoding.  Every served token is the reference's first choice (to
    rounding), the pool is left empty, and the device counters add up."""
    cfg, params, m = build()
    prompts = [tokens(n, seed=10 + n) for n in (5, 13, 21, 9, 30)]
    streamed = [[] for _ in prompts]
    with GenerateServer(cfg, params, slots=2, page_size=4, max_ctx=64, max_steps=20,
                        stream_flush=1, name="tmla") as srv:
        pred = srv.predictor
        assert pred.page_bytes == 3 * 4 * (128 + 16) * 4
        assert isinstance(pred._kv, dict) and len(pred._kv["latent"]) == 3
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
    layers, moe_layers = cfg.n_layers, cfg.n_layers - cfg.n_dense_layers
    assert st["moe_tokens"] == moe_layers * st["active_slot_steps"]
    assert st["moe_pairs_held"] <= st["moe_pairs_at_max_load"] <= 2 * st["moe_pairs_held"]
    scanned = sum(layers * (len(p) + j + 1) for i, p in enumerate(prompts)
                  for j in range(12 + i - 1))
    assert st["dsa_keys_scanned"] == scanned
    assert st["dsa_keys_selected"] == sum(
        layers * min(len(p) + j + 1, cfg.index_topk) for i, p in enumerate(prompts)
        for j in range(12 + i - 1))
    assert 0 < st["moe_pairs_held"] <= st["moe_tokens"] * 2
    assert st["moe_experts_touched"] <= st["moe_pairs_held"]
    assert st["moe_expert_load_max_over_mean"] >= 1.0


def test_bfloat16_weights_are_bound_as_given():
    """A ``jax.Array`` is bound without a copy through the host and in its
    own type: 3.9 B bfloat16 parameters have no float32 twin."""
    cfg, params, _m = build(dtype="bfloat16")
    params = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    pred = GenerativePredictor(cfg, params, slots=2, page_size=4, max_ctx=32)
    assert all(pred._params[k] is params[k] for k in params)
    assert pred._params["q_a_weight"].dtype == jnp.bfloat16
    assert all(a.dtype == jnp.bfloat16 for a in pred._kv["latent"] + pred._kv["index"])
    with pytest.raises(Exception, match="no extend program"):
        pred.extend(np.zeros((1, 4), np.int32), np.zeros((1, 4), np.int32),
                    np.zeros((1, 8), np.int32), np.ones((1, 4), bool))


# -- none of it reaches the transformer's programs ---------------------------
# sha256 of the StableHLO text of the tiny transformer's prefill, decode and
# train step, taken at the commit before this model came (a214a91); the train
# step's replaced in ISSUE 41 (the LayerNorm's and the ReLU's own VJPs change
# what it saves, not prefill or decode), and again when the layer scan came to
# read its weight matrices cast to bfloat16 before it.  A PR that means to
# change those programs says so and replaces the digests:
#   python -c "import tests.test_mla_moe as t; print(t.transformer_digests())"
TRANSFORMER_PROGRAMS = {
    "prefill": "48361dcb20cc12453f83f81f0eea47ea8681ae739ce3db2812c28741c1f7a304",
    "decode": "7373bd46d3bf3fae509f9dc328af67c364658a135ae4e064a5f22e8e1df92c10",
    "train_step": "6b1ee40567717145a93ef6c5985c287846f9f85101f3ffeb3ee952e388483e0e",
}


def transformer_digests():
    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.parallel.mesh import train_mesh

    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64, max_len=64, dtype="bfloat16")
    params = tfm.init_params(cfg, seed=0)
    cache = tfm.init_kv_cache(cfg, num_pages=8, page_size=8)
    step, place = tfm.make_train_step(cfg, train_mesh(jax.devices()[:1], mp=1))
    lowered = {
        "prefill": jax.jit(tfm.make_prefill_fn(cfg, 8)).lower(
            params, cache, jnp.zeros((1, 16), jnp.int32), jnp.int32(9),
            jnp.zeros((2,), jnp.int32)),
        "decode": jax.jit(tfm.make_decode_fn(cfg, 2, 4, 8)).lower(
            params, cache, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), bool)),
        "train_step": step.lower(place(params), jnp.zeros((2, 17), jnp.int32)),
    }
    return {k: hashlib.sha256(v.as_text().encode()).hexdigest()
            for k, v in lowered.items()}


_DIGESTS = {}


@pytest.mark.parametrize("program", sorted(TRANSFORMER_PROGRAMS))
def test_transformer_programs_lower_as_before(program):
    if not _DIGESTS:
        # outside the fixture's "highest": the programs as a user lowers them
        with jax.default_matmul_precision(None):
            _DIGESTS.update(transformer_digests())
    assert _DIGESTS[program] == TRANSFORMER_PROGRAMS[program]


def test_import_mxnet_tpu_loads_none_of_it():
    code = ("import sys, mxnet_tpu, mxnet_tpu.serving, mxnet_tpu.models; "
            "bad = [m for m in sys.modules if m.endswith('mla_moe') "
            "or m.startswith('benchmark')]; print(bad); sys.exit(bool(bad))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
