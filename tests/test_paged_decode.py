"""The paged decode-attention kernel and the decode program around it.

On the CPU the kernel runs in interpret mode; its reference is
``_paged_decode_attention`` over the gathered pages, the path the decode
program itself takes off the TPU. The tests that drive the whole program
through the kernel steer ``transformer.kernel_platform`` to "tpu" (the
kernel still interprets: it asks ``kernels.flash_attention``'s own
binding).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import profiler
from mxnet_tpu.kernels.paged_decode import paged_decode_attention
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.serving import GenerateServer

PAGE, PAGES_PER_SLOT, HEADS = 8, 5, 4
MAX_CTX = PAGE * PAGES_PER_SLOT

# lengths of the slots of one call (a slot attends columns < length; 0 is
# an inactive slot)
CASES = {
    "ragged": (3, 17, 40, 9, 26),
    "ends_on_a_page_boundary": (8, 16, 24, 32, 40),
    "starts_a_page": (1, 9, 17, 25, 33),
    "inactive_slots": (0, 12, 0, 40, 0),
    "all_inactive": (0, 0, 0, 0, 0),
    "one_column": (1, 1, 1, 1, 1),
}


def _pool_and_tables(head_dim, dtype, rng, slots):
    hd = HEADS * head_dim
    n_pages = slots * PAGES_PER_SLOT
    pool = jnp.asarray(rng.normal(size=(2, 2, n_pages + 1, PAGE, hd)), dtype)
    # block tables in shuffled page order: no slot's pages are adjacent
    tables = (rng.permutation(n_pages) + 1).reshape(slots, PAGES_PER_SLOT)
    q = jnp.asarray(rng.normal(size=(slots, hd)), dtype)
    return q, pool, tables.astype(np.int32)


def _reference(q, pool, layer, tables, lengths, block_k):
    S = q.shape[0]
    kg, vg = tfm._gather_pages(pool[layer], jnp.asarray(tables), HEADS)
    out = tfm._paged_decode_attention(
        q.reshape(S, HEADS, 1, -1), kg, vg, jnp.asarray(lengths) - 1, block_k)
    return np.asarray(out.astype(jnp.float32)).reshape(S, -1)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_the_gathered_reference(case, head_dim, dtype):
    lengths = np.array(CASES[case], np.int32)
    rng = np.random.RandomState(len(case) + head_dim)
    q, pool, tables = _pool_and_tables(head_dim, jnp.dtype(dtype), rng,
                                       len(lengths))
    # what the slot must not read is poisoned in the kernel's pool: every
    # page past the one that holds its last column (an inactive slot: all
    # of its pages), and the scratch page its unset table entries name
    poisoned = np.array(pool.astype(jnp.float32))
    for b, n in enumerate(lengths):
        poisoned[:, :, tables[b, -(-int(n) // PAGE):]] = np.nan
    poisoned[:, :, 0] = np.nan
    sparse = tables.copy()
    for b, n in enumerate(lengths):
        sparse[b, -(-int(n) // PAGE):] = 0
    for layer in (0, 1):
        got = paged_decode_attention(
            q, jnp.asarray(poisoned, pool.dtype), jnp.int32(layer),
            jnp.asarray(sparse), jnp.asarray(lengths), n_heads=HEADS,
            block_k=2 * PAGE)
        assert got.dtype == q.dtype and got.shape == q.shape
        got = np.asarray(got.astype(jnp.float32))
        assert np.all(np.isfinite(got))
        active = lengths > 0
        assert np.all(got[~active] == 0)        # an inactive slot: zeros
        if active.any():
            want = _reference(q, pool, layer, tables, lengths, 2 * PAGE)
            np.testing.assert_allclose(
                got[active], want[active],
                **({"atol": 2e-2, "rtol": 2e-2} if dtype == "bfloat16"
                   else {"atol": 2e-5, "rtol": 2e-5}))


@pytest.mark.parametrize("block_k", [PAGE, 3 * PAGE, 128])
def test_kernel_block_k_is_whole_pages_and_changes_nothing(block_k):
    """``block_k`` sets how many pages one online-softmax turn holds (at
    least one, at most the slot's table); the result is the same."""
    lengths = np.array(CASES["ragged"], np.int32)
    rng = np.random.RandomState(5)
    q, pool, tables = _pool_and_tables(64, jnp.float32, rng, len(lengths))
    got = paged_decode_attention(q, pool, jnp.int32(1), jnp.asarray(tables),
                                 jnp.asarray(lengths), n_heads=HEADS,
                                 block_k=block_k)
    want = _reference(q, pool, 1, tables, lengths, block_k)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# the decode program: pool carried, K/V row written in place, kernel or
# gathered reference chosen from the platform
# ---------------------------------------------------------------------------
def _cfg(**kw):
    base = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                max_len=64, dtype="float32")
    base.update(kw)
    return tfm.TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, tfm.init_params(cfg, seed=0)


@pytest.fixture(params=["gathered_reference", "kernel"])
def attend_path(request, monkeypatch):
    if request.param == "kernel":
        monkeypatch.setattr(tfm, "kernel_platform", lambda: "tpu")
    return request.param


def test_decode_program_matches_forward_per_token(model, attend_path):
    cfg, params = model
    rng = np.random.RandomState(1)
    S, plen, page = 24, 10, 8
    toks = rng.randint(0, cfg.vocab, (1, S)).astype(np.int32)
    ref = np.asarray(tfm.make_forward_fn(cfg)(params, jnp.asarray(toks)))[0]

    cache = tfm.init_kv_cache(cfg, 16, page)
    prefill = jax.jit(tfm.make_prefill_fn(cfg, page))
    decode = jax.jit(tfm.make_decode_fn(cfg, slots=4, max_pages_per_slot=8,
                                        page_size=page, block_k=16))
    assert ("mx_paged_decode" in decode.lower(
        params, cache, np.zeros((4,), np.int32), np.zeros((4,), np.int32),
        np.zeros((4, 8), np.int32), np.zeros((4,), bool)).as_text(
            debug_info=True)) == (attend_path == "kernel")
    padded = np.zeros((1, 16), np.int32)
    padded[0, :plen] = toks[0, :plen]
    cache, _ = prefill(params, cache, padded, np.int32(plen),
                       np.array([5, 2], np.int32))
    # teacher-forced decode in slot 2, pages growing on the fly, in an
    # order that is not the pool's
    bt = np.zeros((4, 8), np.int32)
    bt[2, :2] = [5, 2]
    free = [7, 1, 4, 6]
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
        lg = np.asarray(lg)
        np.testing.assert_allclose(lg[2], ref[p], atol=5e-4, rtol=1e-4,
                                   err_msg="position %d" % p)
        assert np.all(lg[[0, 1, 3]] == 0)       # inactive slots: zero logits


def test_decode_step_writes_one_row_a_layer_and_slot(model, attend_path):
    """The pool is carried and updated in place: after a step only
    ``[layer, :, page, offset]`` of the active slots and the scratch page
    (the inactive slots' rows) differ from the pool before it."""
    cfg, params = model
    page, slots = 8, 4
    rng = np.random.RandomState(3)
    before = jnp.asarray(rng.normal(size=tfm.init_kv_cache(cfg, 12, page).shape),
                         jnp.float32)
    decode = jax.jit(tfm.make_decode_fn(cfg, slots=slots, max_pages_per_slot=3,
                                        page_size=page, block_k=8))
    bt = np.array([[4, 9, 0], [7, 0, 0], [3, 11, 2], [0, 0, 0]], np.int32)
    positions = np.array([13, 0, 16, 5], np.int32)
    active = np.array([True, True, True, False])
    tokens = rng.randint(0, cfg.vocab, (slots,)).astype(np.int32)
    after, logits = decode(params, before, tokens, positions, bt, active)
    assert after.shape == before.shape and after.dtype == before.dtype
    changed = np.asarray(after != before)
    expected = np.zeros_like(changed)
    for b in np.flatnonzero(active):
        pg = bt[b, positions[b] // page]
        expected[:, :, pg, positions[b] % page] = True
    assert np.all(changed[:, :, 1:] <= expected[:, :, 1:])
    # every written row really changed (random rows never repeat) ...
    assert np.all(changed[:, :, 1:][expected[:, :, 1:]])
    # ... and the inactive slot's row went to the scratch page
    assert np.all(changed[:, :, 0, positions[3] % page])
    assert np.all(np.isfinite(np.asarray(logits)))


def test_kv_cache_is_lane_dense_and_sharded_over_heads():
    cfg = _cfg(n_heads=4, d_model=32)
    cache = tfm.init_kv_cache(cfg, 6, 8)
    assert cache.shape == (cfg.n_layers, 2, 7, 8, 32)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                             ("dp", "mp"))
    assert tfm.kv_cache_spec(mesh) == jax.sharding.PartitionSpec(
        None, None, None, None, "mp")
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("dp",))
    assert tfm.kv_cache_spec(one) == jax.sharding.PartitionSpec(
        None, None, None, None, None)


def test_broker_counts_the_pages_a_step_reads_and_spans(model):
    """``decode_kv_pages_read``: ceil((position + 1) / page_size) summed
    over the active slots of every decode step; ``_spanned``: slots x
    pages per slot a step."""
    cfg, params = model
    profiler.generate_reset()
    prompts = [np.arange(1, 6, dtype=np.int32), np.arange(1, 20, dtype=np.int32)]
    with GenerateServer(cfg, params, slots=2, page_size=8, max_steps=16,
                        name="tkv") as srv:
        per_slot = srv.predictor.max_pages_per_slot
        # one at a time, so each step's active slot and position are known
        for p in prompts:
            srv.submit(p, max_new_tokens=6).result(timeout=120)
    stats = profiler.generate_stats()
    profiler.generate_reset()
    # prefill emits the first token; the 5 decode steps of a request feed
    # positions len(p) .. len(p) + 4
    want = sum(-(-(len(p) + i + 1) // 8) for p in prompts for i in range(5))
    assert stats["decode_steps"] == 10
    assert stats["decode_kv_pages_read"] == want
    assert stats["decode_kv_pages_spanned"] == 10 * 2 * per_slot
    assert stats["decode_kv_read_share"] == pytest.approx(
        want / (10 * 2 * per_slot))
