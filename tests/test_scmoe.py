"""ISSUE 38 — the shortcut-connected double-block model (``models/scmoe.py``)
against its plain reference (``benchmark/reference/longcat_lm.py``), and
through ``GenerateServer``.  CPU, tiny widths, seeded float32 weights: the
one-shot forward, prefill then decode through the paged latent cache, the
Pallas decode kernel (interpreted) against the ``jax.numpy`` blocked form, the
share of the experts with the identity term once, a token whose choices are
all identity experts, the precision controls, the counters, and that the
module it borrows from lowers as before.
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
from mxnet_tpu.models import scmoe as sm
from mxnet_tpu.serving import GenerateServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.reference import longcat_lm as ref  # noqa: E402
from benchmark.reference import precision  # noqa: E402

TINY = dict(vocab=64, d_model=64, n_heads=4, n_layers=2, d_ff=96, d_expert=32,
            n_experts=16, n_zero_experts=8, experts_per_token=4,
            held_experts=(0, 1, 2, 3), route_scale=6.0, q_rank=32, kv_rank=16,
            d_nope=8, d_rope=8, d_v=8, scale_q_lora=True, scale_kv_lora=True,
            rope_theta=1e4, norm_eps=1e-5, max_len=128, dtype="float32")
# float32 program against a float32 reference, both at "highest": what is left
# is the order of the sums (blocks of keys, absorbed against expanded), a few
# float32 roundings of logits of size 4
TOL = 2e-5


def build(seed=3, **over):
    fields = dict(TINY, **over)
    cfg = sm.ShortcutMoEConfig(**fields)
    return cfg, sm.init_params(cfg, seed=seed, scale=0.15, bias_scale=0.05), fields


def tokens(n, seed=0, vocab=64):
    return np.random.RandomState(seed).randint(0, vocab, n).astype(np.int32)


@pytest.fixture(autouse=True)
def _exact():
    profiler.generate_reset()
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("layers", [1, 2])
def test_forward_matches_the_reference(layers):
    """One double layer alone and two, one-shot, on logits."""
    cfg, params, m = build(n_layers=layers)
    tok = tokens(40)
    want = np.asarray(ref.logits(params, jnp.asarray(tok), m))
    got = np.asarray(sm.make_forward_fn(cfg)(params, jnp.asarray(tok)))
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("switch", ["scale_q_lora", "scale_kv_lora"])
def test_the_low_rank_scalings_are_in_the_mathematics(switch):
    """Each switch off moves the logits (so the factor is applied), and
    program and reference move alike."""
    cfg, params, m = build(**{switch: False})
    tok = tokens(24)
    want = np.asarray(ref.logits(params, jnp.asarray(tok), m))
    got = np.asarray(sm.make_forward_fn(cfg)(params, jnp.asarray(tok)))
    with_it = np.asarray(ref.logits(params, jnp.asarray(tok), dict(m, **{switch: True})))
    assert np.abs(got - want).max() < TOL
    assert np.abs(with_it - want).max() > 100 * TOL


def _serve_by_hand(cfg, params, tok, n_prompt, page=4, bucket=32, block_k=8):
    """Prefill ``n_prompt`` tokens, then decode the rest one at a time in
    slot 1 of 2 (slot 0 idle); logits of every position from n_prompt - 1."""
    cache = sm.init_kv_cache(cfg, 20, page)
    prefill = jax.jit(sm.make_prefill_fn(cfg, page))
    decode = jax.jit(sm.make_decode_fn(cfg, 2, 16, page, block_k=block_k))
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
        counts.append(dict(zip(sm.DECODE_COUNTERS, np.asarray(count).tolist())))
    return np.stack(out), counts


@pytest.mark.parametrize("n_prompt,block_k", [(5, 8), (21, 4), (9, 64)])
def test_prefill_then_decode_matches_the_reference_full_forward(n_prompt, block_k):
    """Through the paged latent cache: the prompt in the expanded form, every
    later token in the absorbed one over all cached rows, in key blocks
    smaller than, equal to and larger than the cache."""
    cfg, params, m = build()
    tok = tokens(40)
    want = np.asarray(ref.logits(params, jnp.asarray(tok), m))
    got, counts = _serve_by_hand(cfg, params, tok, n_prompt, block_k=block_k)
    assert np.abs(got - want[n_prompt - 1:]).max() < TOL
    k = cfg.experts_per_token
    for t, c in zip(range(n_prompt, len(tok)), counts):
        assert c["attn_rows_read"] == 2 * cfg.n_layers * (t + 1)
        assert c["moe_tokens"] == cfg.n_layers       # one active slot
        assert c["moe_pairs_held"] + c["moe_pairs_zero"] <= k * cfg.n_layers
        assert c["moe_experts_touched"] <= c["moe_pairs_held"]
        assert c["moe_pairs_held"] <= c["moe_pairs_at_max_load"]


def test_decode_kernel_interpreted_matches_the_blocked_form():
    """The Pallas kernel's mathematics on the CPU (interpret mode) against
    ``blocked_attention``: slots of length 0, 1, mid-page and a full table,
    pages in a shuffled order, blocks smaller and larger than a slot."""
    from mxnet_tpu.kernels.mla_paged_decode import mla_paged_decode_attention

    rng = np.random.RandomState(0)
    S, H, W, page, P = 4, 8, 128, 4, 40
    pool = jnp.asarray(rng.randn(P + 1, page, W).astype(np.float32))
    q = jnp.asarray(rng.randn(S, H, W).astype(np.float32))
    table = jnp.asarray(rng.permutation(np.arange(1, P + 1)).reshape(S, 10)
                        .astype(np.int32))
    lengths = jnp.asarray([0, 1, 17, 40], jnp.int32)
    for block_k in (4, 16, 64):
        want = np.asarray(mm.blocked_attention(q, pool, table, lengths, 64, 0.3, block_k))
        got = np.asarray(mla_paged_decode_attention(
            q, pool, table, lengths, d_value=64, scale=0.3, block_k=block_k,
            interpret=True))
        assert np.abs(want).max() > 1.0 and not got[0].any()
        assert np.abs(got - want).max() < 1e-5, block_k      # order of the sums
    low = mla_paged_decode_attention(
        q.astype(jnp.bfloat16), pool.astype(jnp.bfloat16), table, lengths, d_value=64,
        scale=0.3, block_k=16, interpret=True)
    assert low.dtype == jnp.bfloat16
    # bfloat16 rows and P, and a bfloat16 result of size 2.5: 2^-8 of it
    assert np.abs(np.asarray(low, np.float32) - want).max() < 0.05


def _expert_layer_uncut(h, params, m):
    ep = ref.expert_leaves(params, 0)
    return np.asarray(ref.experts(h, ep, m, lambda x: x))


def test_shares_with_the_identity_term_once_add_up_to_the_uncut_layer():
    """Guide section 4: the held experts' parts of all 8 shares (2 experts
    each), the identity experts' term counted once, are the reference's uncut
    expert layer; the dense path is outside the expert layer and runs once."""
    cfg, params, m = build(n_layers=1, held_experts=tuple(range(16)))
    h = jnp.asarray(np.random.RandomState(1).randn(24, 64).astype(np.float32))
    uncut = _expert_layer_uncut(h, params, m)
    every = jnp.ones((24,), bool)
    none = sm.ShortcutMoEConfig(**dict(m, held_experts=()))
    ids, gates = sm._route(h, sm._expert_layer(params, 0), none, every)
    identity = np.asarray(jnp.sum(jnp.where(ids >= cfg.n_experts, gates, 0.0),
                                  axis=-1)[:, None] * h)
    total, pairs, zero = identity.copy(), 0, None
    for rank in range(8):
        held = (2 * rank, 2 * rank + 1)
        share = sm.ShortcutMoEConfig(**dict(m, held_experts=held))
        cut = dict(params)
        for k in ("expert_gate_weight.0", "expert_up_weight.0", "expert_down_weight.0"):
            cut[k] = params[k][2 * rank:2 * rank + 2]
        y, counts = sm._moe(h, sm._expert_layer(cut, 0), share, jnp.float32, every)
        total += np.asarray(y) - identity
        pairs += int(counts["moe_pairs_held"])
        zero = int(counts["moe_pairs_zero"])
    assert pairs + zero == 24 * cfg.experts_per_token    # every pair counted once
    assert 0 < zero < pairs
    assert np.abs(uncut).max() > 0.1
    assert np.abs(total - uncut).max() < TOL


def test_a_token_that_chooses_only_identity_experts():
    """A router bias that lifts the identity experts above every real one:
    ``m = (sum g) h1``, no pair on a held expert, no expert touched."""
    cfg, params, m = build(n_layers=1)
    bias = np.zeros((24,), np.float32)
    bias[16:20] = 10.0                       # four identity experts, top-4
    params = dict(params, **{"router_bias.0": jnp.asarray(bias)})
    h = jnp.asarray(np.random.RandomState(2).randn(6, 64).astype(np.float32))
    ep = sm._expert_layer(params, 0)
    y, counts = sm._moe(h, ep, cfg, jnp.float32, jnp.ones((6,), bool))
    p = jax.nn.softmax(h @ params["router_weight.0"], axis=-1)
    gate = cfg.route_scale * jnp.sum(p[:, 16:20], axis=-1)
    assert np.abs(np.asarray(y - gate[:, None] * h)).max() < 1e-6
    assert int(counts["moe_pairs_held"]) == 0 and int(counts["moe_experts_touched"]) == 0
    assert int(counts["moe_pairs_zero"]) == 6 * 4 and int(counts["moe_tokens"]) == 6
    assert np.abs(_expert_layer_uncut(h, params, m) - np.asarray(y)).max() < TOL


def _gaps(params, m, prompt, served):
    seq = np.concatenate([prompt, np.asarray(served, np.int32)])[:-1]
    logits = np.asarray(ref.logits(params, jnp.asarray(seq), m))[len(prompt) - 1:]
    return logits.max(axis=-1) - logits[np.arange(len(served)), served]


def test_generate_server_serves_it_under_page_growth_and_slot_reuse():
    """Five requests on two slots through ``submit`` and the unchanged
    ``PagePool`` and broker: slots and pages are reused, prompts cross page
    boundaries and every stream grows new pages while decoding.  Every served
    token is the reference's first choice (to rounding), the pool is left
    empty, and the device counters add up."""
    cfg, params, m = build()
    prompts = [tokens(n, seed=10 + n) for n in (5, 13, 21, 9, 30)]
    streamed = [[] for _ in prompts]
    with GenerateServer(cfg, params, slots=2, page_size=4, max_ctx=64, max_steps=20,
                        stream_flush=1, name="tscmoe") as srv:
        pred = srv.predictor
        assert pred.page_bytes == 2 * cfg.n_layers * 4 * 128 * 4
        assert isinstance(pred._kv, dict) and len(pred._kv["latent"]) == 2 * cfg.n_layers
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
    assert st["moe_tokens"] == cfg.n_layers * st["active_slot_steps"]
    assert st["attn_rows_read"] == sum(
        2 * cfg.n_layers * (len(p) + j + 1) for i, p in enumerate(prompts)
        for j in range(12 + i - 1))
    assert 0 < st["moe_pairs_zero"] < cfg.experts_per_token * st["moe_tokens"]
    assert 0 < st["moe_pairs_held"] <= st["moe_pairs_at_max_load"]
    assert st["moe_experts_touched"] <= st["moe_pairs_held"]
    assert st["moe_expert_load_max_over_mean"] >= 1.0


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


# -- the pieces it borrows still lower as before ------------------------------
# sha256 of the StableHLO text of the tiny GLM-5-shaped prefill and decode of
# ``models/mla_moe.py``, taken at the commit before this model came (3f00fb6),
# and of the tiny Sarvam-105B-shaped ones (a full-rank query, q/k norm, YaRN,
# no indexer; dense decode in the blocked form), taken at the commit before
# hyper-connections, shared index selections, gates and sinks came (62dccb2).
# A PR that means to change those programs says so and replaces the digests:
#   python -c "import tests.test_scmoe as t; print(t.latent_moe_digests())"
LATENT_MOE_PROGRAMS = {
    "prefill": "cd5754b0df3b55127f537906b21a68cdea34051d8b371627f4c48cece4f8bc98",
    "decode": "f61a87396735936225b8c4fcc0619c6729874a5e7496b0175cb9df370d29142a",
    "sarvam_prefill": "eb068fc4830ccad17ba57faf273bbec7c4b8ad474356083dcadfbe26f2a36197",
    "sarvam_decode": "538afcf4e51b8a4bfd7b160d428ce6c84a6656b98e4c9736193441905d1b6db2",
}


def latent_moe_digests():
    tiny = dict(vocab=64, d_model=64, n_heads=4, n_layers=2, n_dense_layers=1, d_ff=96,
                d_expert=32, n_experts=32, experts_per_token=4, held_experts=(0, 1),
                kv_rank=16, d_nope=8, d_rope=8, d_v=16, rope_theta=1e4, max_len=128,
                dtype="bfloat16")
    configs = {
        "": (mm.LatentMoEConfig(q_rank=32, index_heads=4, index_dim=16, index_rope_dim=8,
                                index_topk=8, **tiny), None),
        "sarvam_": (mm.LatentMoEConfig(q_rank=0, indexer=False, qk_norm=True,
                                       yarn_factor=40.0, yarn_original=4096, **tiny), 8),
    }
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    out = {}
    for name, (cfg, block_k) in configs.items():
        params = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16)
                  for k, (s, _kind) in mm.param_shapes(cfg).items()}
        cache = jax.eval_shape(lambda cfg=cfg: mm.init_kv_cache(cfg, 20, 4))
        lowered = {
            "prefill": jax.jit(mm.make_prefill_fn(cfg, 4)).lower(
                params, cache, i32(1, 32), i32(), i32(8)),
            "decode": jax.jit(mm.make_decode_fn(cfg, 2, 16, 4, block_k=block_k)).lower(
                params, cache, i32(2), i32(2), i32(2, 16),
                jax.ShapeDtypeStruct((2,), jnp.bool_)),
        }
        out.update({name + k: hashlib.sha256(v.as_text().encode()).hexdigest()
                    for k, v in lowered.items()})
    return out


_DIGESTS = {}


@pytest.mark.parametrize("program", sorted(LATENT_MOE_PROGRAMS))
def test_latent_moe_programs_lower_as_before(program):
    if not _DIGESTS:
        # outside the fixture's "highest": the programs as a user lowers them
        with jax.default_matmul_precision(None):
            _DIGESTS.update(latent_moe_digests())
    assert _DIGESTS[program] == LATENT_MOE_PROGRAMS[program]


# sha256 of the StableHLO text of the tiny prefill and decode of this module
# (``models/scmoe.py``), taken at the commit before its dense decode attention
# moved into ``models/mla_moe.py`` (2f71e1e): the move changed no operation.
#   python -c "import tests.test_scmoe as t; print(t.shortcut_moe_digests())"
SHORTCUT_MOE_PROGRAMS = {
    "prefill": "ddc79fd1adb6031f89d32aee969071bfd3ae28c94584a607cff7a75efc54cad5",
    "decode": "9f21ea5f4b5d78e72a1f1a603768844b79b43315a7599cb3d15a399e571c1175",
}


def shortcut_moe_digests():
    cfg = sm.ShortcutMoEConfig(**dict(TINY, dtype="bfloat16"))
    params = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16)
              for k, (s, _kind) in sm.param_shapes(cfg).items()}
    cache = jax.eval_shape(lambda: sm.init_kv_cache(cfg, 20, 4))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    lowered = {
        "prefill": jax.jit(sm.make_prefill_fn(cfg, 4)).lower(
            params, cache, i32(1, 32), i32(), i32(8)),
        "decode": jax.jit(sm.make_decode_fn(cfg, 2, 16, 4, block_k=8)).lower(
            params, cache, i32(2), i32(2), i32(2, 16),
            jax.ShapeDtypeStruct((2,), jnp.bool_)),
    }
    return {k: hashlib.sha256(v.as_text().encode()).hexdigest()
            for k, v in lowered.items()}


@pytest.mark.parametrize("program", sorted(SHORTCUT_MOE_PROGRAMS))
def test_shortcut_moe_programs_lower_as_before(program):
    if "sc_" + program not in _DIGESTS:
        with jax.default_matmul_precision(None):
            _DIGESTS.update({"sc_" + k: v for k, v in shortcut_moe_digests().items()})
    assert _DIGESTS["sc_" + program] == SHORTCUT_MOE_PROGRAMS[program]


def test_the_dense_decode_attention_is_mla_moe_s_own():
    """One absorbed decode attention over the paged cache serves both latent
    modules: this one calls ``mla_moe.paged_attention`` and keeps no copy of
    it, of the blocked form or of the choice of the kernel."""
    import inspect

    src = inspect.getsource(sm)
    assert "mm.paged_attention(" in src and not hasattr(sm, "blocked_attention")
    for copy in ("def blocked_attention", "mla_paged_decode_attention", "kernel_platform",
                 '"shr,rhe->she"'):
        assert copy not in src, copy


def test_import_mxnet_tpu_loads_none_of_it():
    code = ("import sys, mxnet_tpu, mxnet_tpu.serving, mxnet_tpu.models; "
            "bad = [m for m in sys.modules if m.endswith(('scmoe', 'mla_moe', "
            "'mla_paged_decode')) or m.startswith('benchmark')]; "
            "print(bad); sys.exit(bool(bad))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
