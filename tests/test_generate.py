"""Generative serving (ISSUE 12): paged KV cache + incremental decode +
continuous batching.

The two acceptance invariants:

- **Numerical**: prefill + single-token decode against the paged cache
  reproduces the one-shot full-sequence forward per token to
  accumulation-order tolerance (``test_prefill_decode_matches_forward``).
- **Accounting**: the page pool is exact — every page returns after a
  mixed-length run, exhaustion is typed backpressure, never an OOM or a
  silent stall (``test_no_page_leak_after_mixed_length_run``,
  ``test_pool_exhaustion_*``).
"""
import json
import time

import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu import chaos, config, profiler
from mxnet_tpu.kernels.flash_attention import effective_blocks
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.serving import (
    DeadlineExceeded,
    GenerateError,
    GenerateServer,
    GenerativePredictor,
    PagePool,
    PagePoolExhausted,
    ServerClosed,
)


def _cfg(**kw):
    base = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                max_len=64, dtype="float32")
    base.update(kw)
    return tfm.TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, tfm.init_params(cfg, seed=0)


@pytest.fixture(autouse=True)
def _reset_counters():
    profiler.generate_reset()
    yield
    profiler.generate_reset()


# ---------------------------------------------------------------------------
# paged allocator
# ---------------------------------------------------------------------------
def test_page_pool_alloc_free_recycle_interleaved():
    pool = PagePool(6)
    a = pool.alloc(2)
    b = pool.alloc(3)
    assert len(set(a) | set(b)) == 5 and 0 not in a + b
    assert pool.in_use == 5 and pool.high_water == 5
    pool.free(a)                       # completion mid-flight
    c = pool.alloc(3)                  # recycles a's pages + the last free
    assert pool.in_use == 6
    assert set(a) < set(b) | set(c) | set(a)  # ids stay in 1..6
    pool.free(b)
    pool.free(c)
    assert pool.in_use == 0 and pool.free_pages == 6
    s = pool.stats()
    assert s["allocs"] == s["frees"] == 8
    assert s["high_water"] == 6


def test_page_pool_exhaustion_typed_and_all_or_nothing():
    pool = PagePool(3)
    pool.alloc(2)
    with pytest.raises(PagePoolExhausted):
        pool.alloc(2)
    assert pool.in_use == 2            # the failed alloc took nothing
    assert pool.free_pages == 1


def test_page_pool_double_free_raises():
    pool = PagePool(2)
    pages = pool.alloc(1)
    pool.free(pages)
    with pytest.raises(GenerateError):
        pool.free(pages)
    with pytest.raises(GenerateError):
        pool.free([99])


# ---------------------------------------------------------------------------
# decode-shape flash blocks (ISSUE 12 satellite)
# ---------------------------------------------------------------------------
def test_effective_blocks_clamp_decode_shapes():
    # the decode shape: a single query row must clamp to 1, not round
    # up to a 16-row tile
    assert effective_blocks(128, 128, 1, 256) == (1, 128)
    assert effective_blocks(16, 256, 1, 64) == (1, 64)
    # normal shapes keep the 16-row rounding / full-size clamp
    assert effective_blocks(128, 128, 1024, 1024) == (128, 128)
    assert effective_blocks(100, 128, 1024, 64) == (112, 64)


def test_flash_candidates_have_a_decode_search_space():
    from mxnet_tpu import tune

    entries = tune.flash_candidates(1, 256)
    live = [e["schedule"] for e in entries
            if e["status"] in ("default", "candidate")]
    assert all(s["block_q"] == 1 for s in live)
    assert len({s["block_k"] for s in live}) >= 4  # block_k is searched


# ---------------------------------------------------------------------------
# numerical acceptance: prefill + decode == one-shot forward
# ---------------------------------------------------------------------------
def test_prefill_decode_matches_forward(model):
    import jax

    cfg, params = model
    rng = np.random.RandomState(1)
    S, plen, page = 24, 10, 8
    toks = rng.randint(0, cfg.vocab, (1, S)).astype(np.int32)
    ref = np.asarray(tfm.make_forward_fn(cfg)(params, jnp.asarray(toks)))[0]

    cache = tfm.init_kv_cache(cfg, 16, page)
    prefill = jax.jit(tfm.make_prefill_fn(cfg, page))
    decode = jax.jit(tfm.make_decode_fn(cfg, slots=4, max_pages_per_slot=8,
                                        page_size=page, block_k=16))
    padded = np.zeros((1, 16), np.int32)
    padded[0, :plen] = toks[0, :plen]
    cache, logits = prefill(params, cache, padded, np.int32(plen),
                            np.array([1, 2], np.int32))
    np.testing.assert_allclose(np.asarray(logits), ref[plen - 1],
                               atol=5e-5, rtol=1e-5)

    # teacher-forced decode in slot 2, pages growing on the fly
    bt = np.zeros((4, 8), np.int32)
    bt[2, :2] = [1, 2]
    free = [3, 4, 5, 6]
    for p in range(plen, S):
        if bt[2, p // page] == 0:
            bt[2, p // page] = free.pop(0)
        tokens = np.zeros((4,), np.int32)
        tokens[2] = toks[0, p]
        positions = np.zeros((4,), np.int32)
        positions[2] = p
        active = np.zeros((4,), bool)
        active[2] = True
        cache, lg = decode(params, cache, tokens, positions, bt, active)
        np.testing.assert_allclose(np.asarray(lg)[2], ref[p],
                                   atol=5e-4, rtol=1e-4,
                                   err_msg="position %d" % p)


def test_two_slots_interleaved_do_not_cross_talk(model):
    """Two requests decoding in adjacent slots (disjoint pages) each
    reproduce their single-request logits exactly — the paged gather
    reads only the pages a slot's block table names."""
    import jax

    cfg, params = model
    rng = np.random.RandomState(2)
    page, plen, steps = 8, 8, 6
    t_a = rng.randint(0, cfg.vocab, (plen + steps,)).astype(np.int32)
    t_b = rng.randint(0, cfg.vocab, (plen + steps,)).astype(np.int32)
    fwd = tfm.make_forward_fn(cfg)
    ref_a = np.asarray(fwd(params, jnp.asarray(t_a[None])))[0]
    ref_b = np.asarray(fwd(params, jnp.asarray(t_b[None])))[0]

    cache = tfm.init_kv_cache(cfg, 8, page)
    prefill = jax.jit(tfm.make_prefill_fn(cfg, page))
    decode = jax.jit(tfm.make_decode_fn(cfg, slots=2, max_pages_per_slot=4,
                                        page_size=page, block_k=8))
    cache, _ = prefill(params, cache, t_a[None, :plen], np.int32(plen),
                       np.array([1], np.int32))
    cache, _ = prefill(params, cache, t_b[None, :plen], np.int32(plen),
                       np.array([2], np.int32))
    bt = np.zeros((2, 4), np.int32)
    bt[0, :2] = [1, 3]
    bt[1, :2] = [2, 4]
    active = np.ones((2,), bool)
    for i in range(steps):
        p = plen + i
        tokens = np.array([t_a[p], t_b[p]], np.int32)
        positions = np.array([p, p], np.int32)
        cache, lg = decode(params, cache, tokens, positions, bt, active)
        lg = np.asarray(lg)
        np.testing.assert_allclose(lg[0], ref_a[p], atol=5e-4, rtol=1e-4)
        np.testing.assert_allclose(lg[1], ref_b[p], atol=5e-4, rtol=1e-4)


def test_decode_block_k_consults_schedule_table(model, tmp_path,
                                                monkeypatch):
    from mxnet_tpu import tune

    cfg, params = model
    monkeypatch.setenv("MXNET_TPU_TUNE_TABLE",
                       str(tmp_path / "table.json"))
    tune.reset()
    try:
        shape = tfm.decode_schedule_shape(cfg, 2, 32)
        assert shape == (2, cfg.n_heads, 1, 32,
                         cfg.d_model // cfg.n_heads, 0)
        tune.get_table().record(
            "flash_attention", shape, "float32", "cpu",
            {"schedule": {"block_q": 1, "block_k": 8}})
        pred = GenerativePredictor(cfg, params, slots=2, page_size=8,
                                   max_ctx=32)
        assert pred.block_k == 8
        # a different slot count misses the table -> hand default,
        # clamped to the context bound
        pred2 = GenerativePredictor(cfg, params, slots=3, page_size=8,
                                    max_ctx=32)
        assert pred2.block_k == 32
    finally:
        tune.reset()


# ---------------------------------------------------------------------------
# GenerateServer: the continuous-batching loop
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def server(model):
    cfg, params = model
    srv = GenerateServer(cfg, params, slots=4, page_size=8, max_steps=16,
                         name="tgen")
    yield srv
    srv.close()


def test_generate_basic_and_result_fields(server):
    r = server.generate(np.arange(1, 9), max_new_tokens=5)
    assert len(r["tokens"]) == 5
    assert r["finish_reason"] == "length"
    assert r["prompt_tokens"] == 8
    assert r["ttft_s"] is not None and r["ttft_s"] > 0
    assert r["latency_s"] >= r["ttft_s"]
    stats = server.stats()
    assert stats["prefills"] >= 1 and stats["tokens"] >= 5
    assert stats["pages_in_use"] == 0
    assert stats["tokens_s"] > 0     # tokens / (prefill+decode) seconds


def test_generate_eos_stops_early(server):
    # greedy decode is deterministic: learn the continuation, then ask
    # for a later token as EOS
    toks = server.generate(np.arange(4, 20), max_new_tokens=6)["tokens"]
    eos = toks[0]
    r = server.generate(np.arange(4, 20), max_new_tokens=12, eos_id=eos)
    assert r["finish_reason"] == "eos"
    assert r["tokens"] == toks[:toks.index(eos) + 1]
    assert server.stats()["pages_in_use"] == 0


def test_stream_fn_flush_interval(model):
    cfg, params = model
    chunks = []
    with GenerateServer(cfg, params, slots=2, page_size=8,
                        stream_flush=2, name="tstream") as srv:
        r = srv.generate(np.arange(1, 9), max_new_tokens=5,
                         stream_fn=chunks.append)
    assert [len(c) for c in chunks] == [2, 2, 1]
    assert [t for c in chunks for t in c] == r["tokens"]


def test_continuous_admission_into_vacated_slot(model):
    """With every slot busy, a short request admitted into a vacated
    slot finishes while the long one is still decoding — the property
    drain-whole-batch cannot have."""
    cfg, params = model
    with GenerateServer(cfg, params, slots=2, page_size=8, max_steps=40,
                        name="tcont") as srv:
        long = srv.submit(np.arange(1, 9), max_new_tokens=40)
        fill = srv.submit(np.arange(2, 10), max_new_tokens=2)
        fill.result(timeout=60)
        late = srv.submit(np.arange(3, 11), max_new_tokens=2)
        late.result(timeout=60)
        assert not long.done()      # continuous: late rode a vacated slot
        assert len(long.result(timeout=60)["tokens"]) == 40


def test_drain_policy_waits_for_whole_batch(model):
    cfg, params = model
    with GenerateServer(cfg, params, slots=2, page_size=8, max_steps=40,
                        admit_policy="drain", name="tdrain") as srv:
        long = srv.submit(np.arange(1, 9), max_new_tokens=30)
        fill = srv.submit(np.arange(2, 10), max_new_tokens=2)
        late = srv.submit(np.arange(3, 11), max_new_tokens=2)
        fill.result(timeout=60)
        late.result(timeout=60)
        # drain admits `late` only after the WHOLE batch (incl. long)
        # finished
        assert long.done()


def test_deadline_shed_at_dequeue_reclaims_nothing(model):
    cfg, params = model
    with GenerateServer(cfg, params, slots=1, page_size=8, max_steps=60,
                        name="tshed") as srv:
        blocker = srv.submit(np.arange(1, 9), max_new_tokens=55)
        doomed = srv.submit(np.arange(2, 10), max_new_tokens=4,
                            deadline=0.001)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=60)
        blocker.result(timeout=60)
        stats = srv.stats()
        assert stats["shed"] == 1
        assert stats["pages_in_use"] == 0


def test_mid_flight_deadline_reclaims_slot_and_pages(model):
    cfg, params = model
    with GenerateServer(cfg, params, slots=2, page_size=8, max_steps=60,
                        name="tmidd") as srv:
        fut = srv.submit(np.arange(1, 9), max_new_tokens=55,
                         deadline=0.15)
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=60)
        stats = srv.stats()
        assert stats["deadline"] == 1
        assert stats["pages_in_use"] == 0
        # the slot serves the next request
        assert len(srv.generate(np.arange(1, 9),
                                max_new_tokens=2)["tokens"]) == 2


def test_max_steps_cap(model):
    cfg, params = model
    with GenerateServer(cfg, params, slots=1, page_size=8, max_steps=3,
                        name="tcap") as srv:
        r = srv.generate(np.arange(1, 9))
        assert r["finish_reason"] == "length"
        assert len(r["tokens"]) == 3
        assert srv.stats()["pages_in_use"] == 0


def test_chaos_generate_stall_reclaimed_by_cap(model, monkeypatch):
    cfg, params = model
    with GenerateServer(cfg, params, slots=2, page_size=8, max_steps=5,
                        name="tchaos") as srv:
        eos = srv.generate(np.arange(1, 9))["tokens"][0]
        monkeypatch.setenv("MXNET_FAULT_SPEC", "generate:stall@req=1")
        chaos.reset_engine()
        try:
            wedged = srv.submit(np.arange(1, 9), eos_id=eos)
            healthy = srv.submit(np.arange(1, 9), eos_id=eos)
            r_w = wedged.result(timeout=60)
            r_h = healthy.result(timeout=60)
        finally:
            monkeypatch.delenv("MXNET_FAULT_SPEC")
            chaos.reset_engine()
        # the wedged request ignored EOS and was finished by the cap;
        # the healthy one still stopped at EOS
        assert r_w["finish_reason"] == "length"
        assert len(r_w["tokens"]) == 5
        assert r_h["finish_reason"] == "eos"
        assert srv.stats()["pages_in_use"] == 0


def test_pool_exhaustion_backpressures_then_recycles(model):
    cfg, params = model
    pred = GenerativePredictor(cfg, params, slots=2, page_size=8,
                               max_ctx=32, pool_bytes=None)
    # shrink the pool below 2 concurrent full prompts: 4-page pool,
    # 3-page prompts
    pred.pool = PagePool(4)
    with GenerateServer(predictor=pred, max_steps=12,
                        name="tbackp") as srv:
        a = srv.submit(np.arange(1, 21), max_new_tokens=8)   # 3 pages
        b = srv.submit(np.arange(2, 22), max_new_tokens=2)   # waits
        rb = b.result(timeout=60)
        ra = a.result(timeout=60)
        assert len(ra["tokens"]) == 8 and len(rb["tokens"]) == 2
        stats = srv.stats()
        assert stats["pages_in_use"] == 0
        assert stats["pages_high_water"] <= 4


def test_pool_exhaustion_never_admittable_fails_typed(model):
    cfg, params = model
    pred = GenerativePredictor(cfg, params, slots=2, page_size=8,
                               max_ctx=32)
    pred.pool = PagePool(2)
    with GenerateServer(predictor=pred, max_steps=4, name="texh") as srv:
        # a 3-page prompt can never fit a 2-page pool: typed failure at
        # submit, not a silent stall in the queue
        with pytest.raises(PagePoolExhausted):
            srv.submit(np.arange(1, 20), max_new_tokens=2)
        # the pool itself stays consistent and serves fitting requests
        assert len(srv.generate(np.arange(1, 9),
                                max_new_tokens=2)["tokens"]) == 2
        assert srv.predictor.pool.in_use == 0


def test_submit_validation_and_oversized_prompt(server):
    with pytest.raises(GenerateError):
        server.submit(np.zeros((0,), np.int32))
    with pytest.raises(GenerateError):
        server.submit(np.arange(64))       # == max_ctx: no room to generate
    with pytest.raises(GenerateError):
        server.submit(np.arange(1, 9), max_new_tokens=0)
    with pytest.raises(GenerateError):
        server.submit(np.arange(1, 9), deadline=-1)
    # out-of-vocab ids would be CLAMPED by the compiled gather,
    # silently diverging from the zero-masking one-shot forward
    with pytest.raises(GenerateError):
        server.submit(np.array([1, 64], np.int32))   # vocab == 64
    with pytest.raises(GenerateError):
        server.submit(np.array([-1, 2], np.int32))


def test_shared_exec_cache_keys_on_geometry(model):
    """Two predictors sharing one ExecutableCache under the SAME model
    name but different page geometry must compile separate programs —
    a reused closure would bake in the wrong page_size and scatter K/V
    at wrong coordinates."""
    from mxnet_tpu.serving import ExecutableCache

    cfg, params = model
    shared = ExecutableCache(None)
    a = GenerativePredictor(cfg, params, slots=2, page_size=8,
                            cache=shared, model_name="m")
    b = GenerativePredictor(cfg, params, slots=2, page_size=16,
                            cache=shared, model_name="m")
    pa = a.prefill(np.arange(1, 7), a.pool.alloc(1))
    pb = b.prefill(np.arange(1, 7), b.pool.alloc(1))
    assert len(shared) == 2       # no silent program reuse
    np.testing.assert_allclose(pa, pb, atol=5e-5, rtol=1e-5)


def test_stats_empty_until_the_tier_runs(model):
    cfg, params = model
    profiler.generate_reset()
    srv = GenerateServer(cfg, params, slots=2, page_size=8, name="tidle")
    try:
        assert profiler.generate_stats() == {}
    finally:
        srv.close()


def test_no_page_leak_after_mixed_length_run(model):
    """The accounting acceptance: N mixed-length requests with
    interleaved completions leave the pool exactly full, asserted via
    generateStats (the ISSUE 12 wording)."""
    cfg, params = model
    rng = np.random.RandomState(7)
    with GenerateServer(cfg, params, slots=3, page_size=8, max_steps=24,
                        name="tleak") as srv:
        futs = []
        for i in range(12):
            plen = int(rng.randint(2, 40))
            futs.append(srv.submit(
                rng.randint(0, cfg.vocab, (plen,)).astype(np.int32),
                max_new_tokens=int(rng.randint(1, 20))))
        for f in futs:
            f.result(timeout=120)
        stats = srv.stats()
        pool = srv.predictor.pool.stats()
    assert stats["finished"] == 12
    assert stats["pages_in_use"] == 0
    assert pool["in_use"] == 0 and pool["free"] == pool["num_pages"]
    assert pool["allocs"] == pool["frees"] > 0
    assert stats["slot_occupancy"] > 0


def test_close_fails_queued_and_inflight_typed(model):
    cfg, params = model
    srv = GenerateServer(cfg, params, slots=1, page_size=8, max_steps=200,
                         name="tclose")
    inflight = srv.submit(np.arange(1, 9), max_new_tokens=190)
    queued = srv.submit(np.arange(2, 10), max_new_tokens=2)
    time.sleep(0.1)
    srv.close()
    with pytest.raises(ServerClosed):
        inflight.result(timeout=10)
    with pytest.raises(ServerClosed):
        queued.result(timeout=10)
    assert srv.predictor.pool.in_use == 0
    with pytest.raises(ServerClosed):
        srv.submit(np.arange(1, 9))


def test_generate_stats_ride_dump_profile(tmp_path, monkeypatch):
    profiler.generate_reset()
    profiler.generate_record(requests=2, decode_steps=3, tokens=5,
                             slot_steps=8, active_slot_steps=5,
                             pages_in_use=0, pages_high_water=7,
                             pool_pages=16, ttfts=[0.01, 0.02])
    out = tmp_path / "profile.json"
    monkeypatch.setitem(profiler._STATE, "filename", str(out))
    profiler.dump_profile()
    payload = json.loads(out.read_text())
    gs = payload["generateStats"]
    assert gs["requests"] == 2
    assert gs["slot_occupancy"] == round(5 / 8, 3)
    assert gs["pages_high_water"] == 7
    assert gs["ttft_p99_ms"] >= gs["ttft_p50_ms"] > 0
    with pytest.raises(ValueError):
        profiler.generate_record(bogus_counter=1)
    profiler.generate_reset()
    assert profiler.generate_stats() == {}


@pytest.mark.parametrize("knob,value", [
    ("MXNET_GENERATE_SLOTS", "0"),
    ("MXNET_GENERATE_PAGE_SIZE", "banana"),
    ("MXNET_GENERATE_POOL_BYTES", "-5"),
    ("MXNET_GENERATE_MAX_STEPS", "1.5"),
    ("MXNET_GENERATE_STREAM_FLUSH", ""),
])
def test_generate_knob_validation(model, knob, value, monkeypatch):
    cfg, params = model
    monkeypatch.setenv(knob, value)
    with pytest.raises(GenerateError) as e:
        GenerateServer(cfg, params, name="tknob")
    assert knob in str(e.value)


def test_pool_bytes_knob_sizes_the_pool(model, monkeypatch):
    cfg, params = model
    pred0 = GenerativePredictor(cfg, params, slots=2, page_size=8)
    # exactly 10 pages worth of budget
    monkeypatch.setenv("MXNET_GENERATE_POOL_BYTES",
                       str(10 * pred0.page_bytes))
    pred = GenerativePredictor(cfg, params, slots=2, page_size=8)
    assert pred.pool.num_pages == 10
    # a budget below one full-context request is a misconfiguration
    monkeypatch.setenv("MXNET_GENERATE_POOL_BYTES",
                       str(2 * pred0.page_bytes))
    with pytest.raises(GenerateError):
        GenerativePredictor(cfg, params, slots=2, page_size=8)


# ---------------------------------------------------------------------------
# the bind casts the leaves the programs read only in the compute dtype
# ---------------------------------------------------------------------------
_STACKS = ("attn_qkv_weight", "attn_out_weight", "ffn_up_weight",
           "ffn_down_weight")


def _bound_dtypes(cfg, params):
    narrow = jnp.dtype(cfg.dtype) != jnp.float32
    return {k: jnp.dtype(cfg.dtype) if narrow and k in _STACKS
            else v.dtype for k, v in params.items()}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bind_casts_the_stacked_matrices_once(dtype):
    """At a bfloat16 compute dtype the four stacked matrices are bound in
    bfloat16, every other leaf as it was handed, and prefill and decode give
    the bits the module's own programs give on the float32 parameters. At
    float32 nothing is cast: every handed array is bound as it is."""
    import jax

    cfg = _cfg(dtype=dtype)
    params = tfm.init_params(cfg, seed=4)
    pred = GenerativePredictor(cfg, params, slots=2, page_size=4, max_ctx=32)
    dtypes = _bound_dtypes(cfg, params)
    for k, v in params.items():
        assert pred._params[k].dtype == dtypes[k], k
        if dtypes[k] == v.dtype:
            assert pred._params[k] is v, k
    stats = pred.pool_stats()
    assert stats["params_cast"] == sum(dtypes[k] != v.dtype
                                       for k, v in params.items())
    assert stats["params_cast"] == (0 if dtype == "float32" else 4)
    assert stats["param_bytes"] == sum(v.size * dtypes[k].itemsize
                                       for k, v in params.items())
    assert stats["in_use"] == 0            # beside the pool's own counters

    n, steps, page = 9, 8, pred.page_size
    prompt = _prompt(n, 5)
    pages = pred.pool.alloc(pred.pages_needed(n + steps))
    first = pred.pages_needed(n)
    bucket = pred.pick_bucket(n)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = prompt
    page_arr = np.zeros((bucket // page,), np.int32)
    page_arr[:first] = pages[:first]
    cache = tfm.init_kv_cache(cfg, pred.pool.num_pages, page)
    cache, want = jax.jit(tfm.make_prefill_fn(cfg, page))(
        params, cache, padded, np.int32(n), page_arr)
    got = pred.prefill(prompt, pages[:first])
    np.testing.assert_array_equal(got, np.asarray(want))

    decode = jax.jit(tfm.make_decode_fn(cfg, 2, pred.max_pages_per_slot,
                                        page, block_k=pred.block_k))
    table = np.zeros((2, pred.max_pages_per_slot), np.int32)
    table[1, :len(pages)] = pages
    active = np.array([False, True])
    token = int(np.argmax(got))
    for i in range(steps):
        tokens = np.array([0, token], np.int32)
        positions = np.array([0, n + i], np.int32)
        cache, want = decode(params, cache, tokens, positions, table, active)
        got = pred.decode(tokens, positions, table, active)
        np.testing.assert_array_equal(got, np.asarray(want),
                                      err_msg="step %d" % i)
        token = int(np.argmax(got[1]))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_mesh_bind_casts_the_stacked_matrices_too(dtype):
    """The sharded bind casts the same leaves to the same bits as the
    one-device bind, each leaf placed as ``param_specs`` shards it."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.parallel.mesh import train_mesh

    cfg = _cfg(dtype=dtype)
    params = tfm.init_params(cfg, seed=4)
    mesh = train_mesh(mp=2)
    specs = tfm.param_specs(cfg, mesh)
    gp = GenerativePredictor(cfg, params, slots=2, page_size=4, max_ctx=32,
                             mesh=mesh)
    gr = GenerativePredictor(cfg, params, slots=2, page_size=4, max_ctx=32)
    dtypes = _bound_dtypes(cfg, params)
    for k, a in gp._params.items():
        assert a.dtype == dtypes[k], k
        assert a.sharding.is_equivalent_to(
            NamedSharding(mesh, specs.get(k, P())), a.ndim), k
        np.testing.assert_array_equal(np.asarray(a), np.asarray(gr._params[k]))
    got, want = gp.pool_stats(), gr.pool_stats()
    assert got["params_cast"] == want["params_cast"] == (
        0 if dtype == "float32" else 4)
    assert got["param_bytes"] == want["param_bytes"]


# ---------------------------------------------------------------------------
# ISSUE 39: the plain decode loop keeps one step in flight. The token is
# chosen inside the decode program and fed to the next step on the device;
# the host reads a step's ids while the next one runs. Every served chain is
# the chain a synchronous loop of pred.decode + np.argmax serves.
# ---------------------------------------------------------------------------
def _transformer():
    cfg = _cfg()
    # larger matrices than init_params draws: the greedy chain of the
    # default draw repeats one token, and an eos case needs a varied one
    params = {k: v * (4.0 if k.endswith("weight") and "embed" not in k
                      else 1.0)
              for k, v in tfm.init_params(cfg, seed=2).items()}
    return cfg, params


def _latent_moe():
    from mxnet_tpu.models import mla_moe

    cfg = mla_moe.LatentMoEConfig(
        vocab=64, d_model=64, n_heads=4, n_layers=3, n_dense_layers=1,
        d_ff=96, d_expert=32, n_experts=32, experts_per_token=4,
        held_experts=(0, 1), route_scale=2.5, q_rank=32, kv_rank=16,
        d_nope=8, d_rope=8, d_v=16, index_heads=4, index_dim=16,
        index_rope_dim=8, index_topk=8, rope_theta=1e4, norm_eps=1e-5,
        index_norm_eps=1e-6, max_len=128, dtype="float32")
    return cfg, mla_moe.init_params(cfg, seed=3, scale=0.15, bias_scale=0.05)


def _shortcut_moe():
    from mxnet_tpu.models import scmoe

    cfg = scmoe.ShortcutMoEConfig(
        vocab=64, d_model=64, n_heads=4, n_layers=2, d_ff=96, d_expert=32,
        n_experts=16, n_zero_experts=8, experts_per_token=4,
        held_experts=(0, 1, 2, 3), route_scale=6.0, q_rank=32, kv_rank=16,
        d_nope=8, d_rope=8, d_v=8, scale_q_lora=True, scale_kv_lora=True,
        rope_theta=1e4, norm_eps=1e-5, max_len=128, dtype="float32")
    return cfg, scmoe.init_params(cfg, seed=3, scale=0.15, bias_scale=0.05)


_MODULES = {"transformer": _transformer, "mla_moe": _latent_moe,
            "scmoe": _shortcut_moe}
_BUILT = {}


@pytest.fixture(params=sorted(_MODULES))
def any_model(request):
    """(config, params) of each of the three model modules, tiny."""
    if request.param not in _BUILT:
        _BUILT[request.param] = _MODULES[request.param]()
    return _BUILT[request.param]


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 64, n).astype(np.int32)


def _host_chain(cfg, params, prompt, n, page_size=4, max_ctx=64):
    """The chain a synchronous loop serves: prefill, then pred.decode and
    np.argmax on the host, one step at a time."""
    pred = GenerativePredictor(cfg, params, slots=2, page_size=page_size,
                               max_ctx=max_ctx)
    pages = pred.pool.alloc(pred.pages_needed(len(prompt) + n))
    table = np.zeros((2, pred.max_pages_per_slot), np.int32)
    table[1, :len(pages)] = pages
    first = pred.pages_needed(len(prompt))
    chain = [int(np.argmax(pred.prefill(prompt, pages[:first])))]
    active = np.array([False, True])
    for i in range(n - 1):
        logits = pred.decode([0, chain[-1]], [0, len(prompt) + i], table,
                             active)
        assert logits.shape == (2, cfg.vocab)
        assert not logits[0].any()           # the idle slot: zero logits
        chain.append(int(np.argmax(logits[1])))
    return chain


def _serve(cfg, params, **kw):
    kw = dict(dict(slots=2, page_size=4, max_ctx=64, max_steps=40,
                   stream_flush=1, name="tahead"), **kw)
    return GenerateServer(cfg, params, **kw)


def test_served_chain_is_the_host_loops_chain(any_model):
    cfg, params = any_model
    prompt = _prompt(9, 1)
    want = _host_chain(cfg, params, prompt, 24)
    with _serve(cfg, params) as srv:
        got = srv.generate(prompt, max_new_tokens=24)
        # one program, whichever side a slot's token comes from
        assert srv.predictor._decode_exec()._cache_size() == 1
    assert got["tokens"] == want and got["finish_reason"] == "length"
    st = profiler.generate_stats()
    assert st["decode_steps"] == 23 == st["active_slot_steps"]
    assert st["decode_steps_ahead"] == 22
    assert st["decode_tokens_discarded"] == 0


def _stats_once(**reached):
    """generate_stats once its counters reach the given values: a future
    resolves when its request finishes, and the loop reads the step it
    had dispatched ahead a turn later."""
    give_up = time.monotonic() + 30
    while True:
        st = profiler.generate_stats()
        if all(st.get(k) == v for k, v in reached.items()) \
                or time.monotonic() > give_up:
            return st
        time.sleep(0.002)


def _late_eos(chain):
    """A token of the chain first seen at index 2 or later, so an eos on it
    arrives while the next step is in flight."""
    for i, t in enumerate(chain):
        if 2 <= i < len(chain) - 2 and t not in chain[:i]:
            return i, t
    raise AssertionError("no token to stop on in %r" % (chain,))


def test_eos_with_the_next_step_in_flight_drops_the_stray_id(any_model):
    cfg, params = any_model
    prompt, other = _prompt(9, 1), _prompt(11, 5)
    chain = _host_chain(cfg, params, prompt, 24)
    others = _host_chain(cfg, params, other, 10)
    at, eos = _late_eos(chain)
    streamed = []
    with _serve(cfg, params, slots=1) as srv:
        pool = srv.predictor.pool
        r = srv.generate(prompt, max_new_tokens=24, eos_id=eos,
                         stream_fn=streamed.extend)
        assert r["finish_reason"] == "eos"
        assert r["tokens"] == chain[:at + 1] == streamed
        # the step dispatched ahead of the eos ran for the slot once more:
        # its id is dropped, neither streamed nor counted
        st = _stats_once(decode_tokens_discarded=1)
        assert st["decode_tokens_discarded"] == 1
        assert st["decode_steps"] == at + 1 and st["active_slot_steps"] == at
        assert st["tokens"] == at + 1
        assert pool.in_use == 0 and pool.allocs == pool.frees
        first = pool.allocs
        # the one slot and its recycled pages serve the next request right
        nxt = srv.generate(other, max_new_tokens=10)
        assert nxt["tokens"] == others
        assert pool.in_use == 0 and pool.allocs == pool.frees > first


def test_length_finish_runs_no_stray_slot_step(model):
    cfg, params = model
    with _serve(cfg, params, page_size=8) as srv:
        futures = [srv.submit(_prompt(6 + i, i), max_new_tokens=n)
                   for i, n in enumerate((3, 17, 9))]
        assert [len(f.result(timeout=60)["tokens"]) for f in futures] \
            == [3, 17, 9]
    st = profiler.generate_stats()
    # a token a decode step but the prefill's, and no slot step beyond
    assert st["active_slot_steps"] == 2 + 16 + 8
    assert st["tokens"] == 3 + 17 + 9 and st["length"] == 3
    assert st["decode_tokens_discarded"] == 0
    assert st["pages_in_use"] == 0


def test_admitted_with_a_step_in_flight_takes_its_prefills_token(any_model):
    cfg, params = any_model
    long_p, late_p = _prompt(9, 1), _prompt(13, 7)
    want_long = _host_chain(cfg, params, long_p, 40)
    want_late = _host_chain(cfg, params, late_p, 12)
    seen = []
    with _serve(cfg, params) as srv:
        long = srv.submit(long_p, max_new_tokens=40, stream_fn=seen.extend)
        while len(seen) < 5:            # decoding, a step in flight
            time.sleep(0.001)
        late = srv.submit(late_p, max_new_tokens=12)
        assert late.result(timeout=60)["tokens"] == want_late
        assert long.result(timeout=60)["tokens"] == want_long == seen
    st = profiler.generate_stats()
    assert st["decode_tokens_discarded"] == 0
    assert st["active_slot_steps"] == 39 + 11


def test_deadline_that_expires_mid_stream(model):
    cfg, params = _transformer()
    prompt = _prompt(9, 1)
    chain = _host_chain(cfg, params, prompt, 50, page_size=8)
    seen = []
    with _serve(cfg, params, page_size=8, max_steps=60) as srv:
        # compile first: the deadline is to pass between two tokens
        assert srv.generate(prompt, max_new_tokens=6)["tokens"] == chain[:6]
        _stats_once(decode_steps=5)
        profiler.generate_reset()
        srv._step_hook = lambda: time.sleep(0.01)
        fut = srv.submit(prompt, max_new_tokens=50, deadline=0.2,
                         stream_fn=seen.extend)
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=60)
        srv._step_hook = None
        st = srv.stats()
        assert 0 < len(seen) < 50 and seen == chain[:len(seen)]
        assert st["deadline"] == 1 and st["tokens"] == len(seen)
        assert st["pages_in_use"] == 0
        # the slot and its pages serve the next request
        assert srv.generate(prompt, max_new_tokens=6)["tokens"] == chain[:6]
    # the step in flight when the deadline passed was dropped, once read
    assert profiler.generate_stats()["decode_tokens_discarded"] == 1


def test_close_with_a_step_in_flight_fails_every_future(model):
    cfg, params = model
    seen = []
    srv = _serve(cfg, params, page_size=8, max_steps=60, slots=1)
    srv._step_hook = lambda: time.sleep(0.005)
    running = srv.submit(_prompt(8, 0), max_new_tokens=50,
                         stream_fn=seen.extend)
    queued = srv.submit(_prompt(8, 1), max_new_tokens=4)
    while len(seen) < 3:
        time.sleep(0.001)
    srv.close()
    for fut in (running, queued):
        with pytest.raises(ServerClosed):
            fut.result(timeout=10)
    assert not srv._thread.is_alive()
    assert srv.predictor.pool.in_use == 0
    assert 3 <= len(seen) < 50


def test_failing_step_with_a_step_in_flight_fails_every_future(model):
    cfg, params = model
    calls = []

    def hook():
        calls.append(1)
        if len(calls) == 4:          # steps 1-3 dispatched, step 3 unread
            raise RuntimeError("step failed")

    srv = _serve(cfg, params, page_size=8, slots=2)
    srv._step_hook = hook
    futures = [srv.submit(_prompt(8, i), max_new_tokens=20)
               for i in range(3)]
    for fut in futures:
        with pytest.raises(RuntimeError, match="step failed"):
            fut.result(timeout=30)
    srv._thread.join(10)
    assert not srv._thread.is_alive()
    assert srv.predictor.pool.in_use == 0
    with pytest.raises(GenerateError.__mro__[1], match="worker died"):
        srv.submit(_prompt(8, 0))
    srv.close()


@pytest.mark.parametrize("flush", [1, 3])
def test_stream_fn_sees_every_token_once_in_order(model, flush):
    cfg, params = _transformer()
    prompts = [_prompt(5 + 3 * i, 20 + i) for i in range(5)]
    seen = [[] for _ in prompts]
    with _serve(cfg, params, page_size=8, stream_flush=flush) as srv:
        futures = [srv.submit(p, max_new_tokens=7 + 4 * i,
                              stream_fn=seen[i].extend)
                   for i, p in enumerate(prompts)]
        results = [f.result(timeout=60) for f in futures]
    for i, (p, r) in enumerate(zip(prompts, results)):
        assert seen[i] == r["tokens"]
        assert r["tokens"] == _host_chain(cfg, params, p, 7 + 4 * i,
                                          page_size=8)
    assert profiler.generate_stats()["tokens"] == sum(map(len, seen))


@pytest.mark.parametrize("answer,ahead", [(1, 0), (2, 0), (40, 38)])
def test_decode_ahead_share_by_answer_length(model, answer, ahead):
    cfg, params = model
    with _serve(cfg, params, page_size=8) as srv:
        assert len(srv.generate(_prompt(8, 0),
                                max_new_tokens=answer)["tokens"]) == answer
    st = profiler.generate_stats()
    assert st["decode_steps"] == answer - 1
    assert st["decode_steps_ahead"] == ahead
    # no step, no share; one step has nothing ahead of it; a long answer
    # has all but its first
    assert st.get("decode_ahead_share", 0) == \
        (ahead / (answer - 1) if answer > 1 else 0)
    assert (answer < 40) or st["decode_ahead_share"] > 0.97


def test_drain_policy_keeps_a_step_in_flight_too(model):
    cfg, params = _transformer()
    prompts = [_prompt(7, 30), _prompt(9, 31), _prompt(11, 32)]
    with _serve(cfg, params, page_size=8, admit_policy="drain") as srv:
        futures = [srv.submit(p, max_new_tokens=12) for p in prompts]
        got = [f.result(timeout=60)["tokens"] for f in futures]
    assert got == [_host_chain(cfg, params, p, 12, page_size=8)
                   for p in prompts]
    st = profiler.generate_stats()
    assert st["decode_ahead_share"] > 0.8 and st["pages_in_use"] == 0


def test_decode_returns_logits_and_counters_as_before(any_model):
    cfg, params = any_model
    pred = GenerativePredictor(cfg, params, slots=2, page_size=4, max_ctx=64)
    pages = pred.pool.alloc(3)
    table = np.zeros((2, pred.max_pages_per_slot), np.int32)
    table[0, :3] = pages
    first = int(np.argmax(pred.prefill(_prompt(7, 3), pages[:2])))
    logits = pred.decode([first, 0], [7, 0], table, [True, False])
    assert isinstance(logits, np.ndarray) and logits.shape == (2, cfg.vocab)
    assert set(pred.step_counters) == set(pred._counter_names)
    # the second entry runs the same program and reads nothing; what it
    # returns holds the ids the logits put first, and the counters
    chosen = pred.decode_ahead([first, 0], [True, True], [7, 0], table,
                               [True, False])
    ids, counters = pred.read_step(chosen)
    assert ids.dtype == np.int32 and ids.tolist() == [
        int(np.argmax(logits[0])), 0]
    assert counters == pred.step_counters
    # and a token left on the device is the one the host would have sent
    nxt = pred.decode_ahead([0, 0], [False, False], [8, 0], table,
                            [True, False])
    want = pred.decode([int(ids[0]), 0], [8, 0], table, [True, False])
    assert pred.read_step(nxt)[0][0] == int(np.argmax(want[0]))
