"""The serving decode program compiled at the benchmark's own size for a
TPU v5e that is described, not attached (the chip's compiler is installed
here): what interpret mode cannot show. The Mosaic kernel is accepted at
real widths, the page pool is updated in place, and the program holds no
second pool. Nothing runs, so nothing here is a time.

All such compiles live in this one file: only the worker that is given it
loads the TPU's library, and only once a test has started (the topology is
described inside a fixture)."""
import math
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.models import transformer as tfm


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from loading
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    import mxnet_tpu.kernels  # noqa: F401  (loads kernels.flash_attention)
    for name in ("mxnet_tpu.models.transformer",
                 "mxnet_tpu.kernels.flash_attention"):
        monkeypatch.setattr(sys.modules[name], "kernel_platform",
                            lambda: "tpu")


def _param_shapes(cfg):
    """``init_params``' layout without its host draw of every weight (a
    minute at 1.3 B parameters)."""
    L, d, f, H = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_heads
    return {"embed_weight": (cfg.vocab, d), "pos_embed_weight": (cfg.max_len, d),
            "final_ln_gamma": (d,), "final_ln_beta": (d,),
            "ln1_gamma": (L, d), "ln1_beta": (L, d),
            "ln2_gamma": (L, d), "ln2_beta": (L, d),
            "attn_qkv_weight": (L, d, 3, H, d // H),
            "attn_out_weight": (L, H, d // H, d),
            "ffn_up_weight": (L, d, f), "ffn_down_weight": (L, f, d)}


def test_param_shapes_are_init_params_own():
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                d_ff=48, max_len=16)
    assert _param_shapes(cfg) == {k: v.shape for k, v in
                                  tfm.init_params(cfg).items()}


def test_opt_1_3b_decode_holds_one_pool_and_reads_it_in_place(one_chip, on_tpu):
    # benchmark/configs/opt-1.3b.json and benchmark/traffic/serve-chat.json
    cfg = tfm.TransformerConfig(vocab=50272, d_model=2048, n_heads=32,
                                n_layers=24, d_ff=8192, max_len=2048,
                                dtype="bfloat16")
    slots, page, per_slot = 16, 16, 1216 // 16

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = {k: jax.ShapeDtypeStruct(v, jnp.float32, sharding=one_chip)
              for k, v in _param_shapes(cfg).items()}
    cache = described(jax.eval_shape(
        lambda: tfm.init_kv_cache(cfg, slots * per_slot, page)))
    pool_bytes = 2 * math.prod(cache.shape)
    fn = jax.jit(tfm.make_decode_fn(cfg, slots, per_slot, page),
                 donate_argnums=(1,))
    compiled = fn.lower(
        params, cache,
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots, per_slot), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mx_paged_decode" in text
    # the donated pool comes back as the output, and the temporaries (the
    # matrices cast to bfloat16, 2.42 GB) hold no second pool
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes
    # the pool keeps its own order on the device (a page is contiguous) ...
    shape = r"bf16\[%s\]" % ",".join(map(str, cache.shape))
    assert re.search(shape + r"\{4,3,2,1,0:T\(8,128\)\(2,1\)\} parameter\(",
                     text)
    # ... and nothing copies, pads or slices an array of its size
    assert not re.search(r"= %s\S* (copy|pad|slice|dynamic-slice|transpose)\("
                         % shape, text)
    # nor one of the gathered keys' and values' size
    assert "bf16[16,32,1216,64]" not in text


def test_lm_cell_attention_feeds_three_flash_kernels_head_dim_64(one_chip,
                                                                 on_tpu):
    # benchmark/configs/opt-1.3b-train.json at the cell's batch: what
    # make_train_step's _block hands _attention, forward and backward
    shape = jax.ShapeDtypeStruct((2, 32, 2048, 64), jnp.bfloat16,
                                 sharding=one_chip)

    def attend(q, k, v):
        return tfm._attention(q, k, v, axes=(), causal=True)

    def step(q, k, v, do):
        out, vjp = jax.vjp(attend, q, k, v)
        return (out,) + vjp(do)

    compiled = jax.jit(step).lower(shape, shape, shape, shape).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # one call each, named by pl.pallas_call(name=): the benchmark's
    # flash_fwd / flash_dq / flash_dkv_device_ms_per_step match on these
    for name in ("mx_flash_fwd", "mx_flash_dq", "mx_flash_dkv"):
        assert sum(name in line for line in calls) == 1, name
    assert len(calls) == 3
    for line in calls:
        operands = line.split("operand_layout_constraints={")[1].split("}, ")[0]
        # q, k, v, do as they arrive: head dim 64, no pad to 128 lanes
        assert "bf16[64,2048,64]" in operands
        assert "bf16[64,2048,128]" not in line
    assert not re.search(r"bf16\[[\d,]*,128\]\S* pad\(", text)
    # lse and delta have the sequence minor: a trailing dim of 1 would be
    # padded to 128 lanes (67 MB a layer where 0.5 MB is data)
    assert "f32[64,1,2048]" in text and "f32[64,2048,1]" not in text
    # every block fits the default scoped VMEM (compile() raised otherwise),
    # and nothing of the size of the scores is held between the kernels
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 64 * 2048 * 2048


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_kernels_compile_under_an_ambient_highest_precision(
        one_chip, on_tpu, dtype):
    # chip_smoke's parity check runs the kernels inside
    # jax.default_matmul_precision("highest"): Mosaic refuses an fp32
    # contraction of bfloat16 operands ("Bad lhs type"), so the kernels'
    # bfloat16 products must not inherit it; float32 operands may
    shape = jax.ShapeDtypeStruct((1, 8, 1024, 64), dtype, sharding=one_chip)

    def step(q, k, v, do):
        out, vjp = jax.vjp(
            lambda *a: tfm._attention(*a, axes=(), causal=True), q, k, v)
        return (out,) + vjp(do)

    with jax.default_matmul_precision("highest"):
        text = jax.jit(step).lower(shape, shape, shape, shape).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_longcat_decode_reads_its_eight_latent_pools_in_place(one_chip, monkeypatch):
    # benchmark/configs/longcat-flash-chat-ep32.json and
    # benchmark/traffic/decode-pool-12k.json: the double-block decode step at
    # the cell's size, 5.17 B bfloat16 parameters as shapes
    import mxnet_tpu.kernels  # noqa: F401  (loads kernels.flash_attention)
    from mxnet_tpu.models import scmoe

    for name in ("mxnet_tpu.models.mla_moe", "mxnet_tpu.kernels.flash_attention"):
        monkeypatch.setattr(sys.modules[name], "kernel_platform", lambda: "tpu")
    cfg = scmoe.ShortcutMoEConfig()
    slots, page, per_slot = 16, 16, 15104 // 16

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
              for k, (s, _kind) in scmoe.param_shapes(cfg).items()}
    cache = jax.tree.map(described, jax.eval_shape(
        lambda: scmoe.init_kv_cache(cfg, slots * per_slot, page)))
    pools = cache["latent"]
    pool_bytes = sum(2 * math.prod(p.shape) for p in pools)
    assert len(pools) == 8 and pool_bytes == scmoe.kv_page_bytes(cfg, page) * (
        slots * per_slot + 1)
    fn = jax.jit(scmoe.make_decode_fn(cfg, slots, per_slot, page,
                                      block_k=scmoe._decode_block_k(cfg, slots, 15104)),
                 donate_argnums=(1,))
    compiled = fn.lower(
        params, cache,
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots, per_slot), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    # one Mosaic call an attention, each over its pool where it lies
    assert text.count('custom_call_target="tpu_custom_call"') == 8
    assert "mx_mla_paged_decode" in text
    # the donated pools come back as the outputs; the temporaries hold no
    # second pool, no gathered rows (16 x 15104 x 640 x 2 B = 309 MB) and no
    # copy of a dense FFN's matrix (151 MB)
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 144 * 2 ** 20
    shape = r"bf16\[%s\]" % ",".join(map(str, pools[0].shape))
    assert re.search(shape + r"\{2,1,0:T\(8,128\)\(2,1\)\} parameter\(", text)
    assert not re.search(r"= %s\S* (copy|pad|slice|dynamic-slice|transpose)\("
                         % shape, text)


def test_sarvam_decode_reads_its_five_latent_pools_in_place(one_chip, monkeypatch):
    # benchmark/configs/sarvam-105b-ep16.json and
    # benchmark/traffic/decode-pool-64k.json: the dense latent decode step of
    # ``mla_moe`` at the cell's size, 1.85 B bfloat16 parameters as shapes, 64-row
    # pages, a block table of up to 1088 pages a slot (a scalar-prefetch
    # operand of the kernel) and the pool the runner sizes in bytes
    import json
    import os

    import mxnet_tpu.kernels  # noqa: F401  (loads kernels.flash_attention)
    from mxnet_tpu.models import mla_moe

    from benchmark import traffic_gen
    from benchmark.runners.serve_decode_pool_sarvam import pool_pages

    for name in ("mxnet_tpu.models.mla_moe", "mxnet_tpu.kernels.flash_attention"):
        monkeypatch.setattr(sys.modules[name], "kernel_platform", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "sarvam-105b-ep16.json")) as f:
        cfg = mla_moe.LatentMoEConfig(**json.load(f)["program"])
    with open(os.path.join(root, "benchmark", "traffic", "decode-pool-64k.json")) as f:
        mix = json.load(f)
    slots, page = mix["slots"], mix["page_size"]
    per_slot = mix["max_ctx"] // page
    prompts = traffic_gen._lognormal_quantiles(
        mix["prompt_tokens"], (np.arange(slots) + 0.5) / slots)
    pages = pool_pages(prompts, mix["answer_tokens"], page, slots, mix["pool_margin"])
    assert pages < 0.65 * slots * per_slot

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
              for k, (s, _kind) in mla_moe.param_shapes(cfg).items()}
    cache = jax.tree.map(described, jax.eval_shape(
        lambda: mla_moe.init_kv_cache(cfg, pages, page)))
    pools = cache["latent"]
    assert list(cache) == ["latent"] and len(pools) == 5
    pool_bytes = sum(2 * math.prod(p.shape) for p in pools)
    assert pool_bytes == mla_moe.kv_page_bytes(cfg, page) * (pages + 1)
    fn = jax.jit(mla_moe.make_decode_fn(
        cfg, slots, per_slot, page,
        block_k=mla_moe._decode_block_k(cfg, slots, per_slot * page)), donate_argnums=(1,))
    compiled = fn.lower(
        params, cache,
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots, per_slot), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    # one Mosaic call a layer, each over its pool where it lies
    assert text.count('custom_call_target="tpu_custom_call"') == 5
    assert "mx_mla_paged_decode" in text
    # the donated pools come back as the outputs; the temporaries hold no
    # second pool and no gathered rows (16 x 68608 x 640 x 2 B = 1.4 GB)
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 96 * 2 ** 20
    shape = r"bf16\[%s\]" % ",".join(map(str, pools[0].shape))
    assert re.search(shape + r"\{2,1,0:T\(8,128\)\(2,1\)\} parameter\(", text)
    assert not re.search(r"= %s\S* (copy|pad|slice|dynamic-slice|transpose)\("
                         % shape, text)


# -- ISSUE 39: the decode step as it is served ---------------------------------
# ``GenerativePredictor`` wraps each module's ``make_decode_fn`` once
# (``serving.generate._decode_program``): the token is chosen on the device and
# can be fed to the next step there. The modules' own programs stay what they
# were: sha256 of the StableHLO text each lowers to for the TPU at its cell's
# sizes, taken at the commit before (6c94e2d). A PR that means to change one
# says so and replaces its digest:
#   python -c "import tests.test_tpu_compile as t; print(t.decode_digests())"
MODULE_DECODE_PROGRAMS = {
    "opt-1.3b.serve-chat":
        "72d700654c3a0e7db8c247bd392929f8df8da70ea0fe3e80be7a0c5a7d447728",
    "glm-5.decode-pool-16k":
        "f9292b53755e26a6d1ccd3f127041541bdc2fd0432c42c5f89203ea5787402a3",
    "longcat-flash.decode-pool-12k":
        "41949d1eb0a3197721cb27a6924453d2becf1b3a4035b2df17906a8e4db095c4",
}


def _served_cells():
    """cell -> (module, config, parameter shapes, slots, pages a slot, page):
    benchmark/configs/*.json and benchmark/traffic/*.json."""
    from mxnet_tpu.models import mla_moe, scmoe

    opt = tfm.TransformerConfig(vocab=50272, d_model=2048, n_heads=32,
                                n_layers=24, d_ff=8192, max_len=2048,
                                dtype="bfloat16")
    glm, longcat = mla_moe.LatentMoEConfig(), scmoe.ShortcutMoEConfig()

    def table(mod, cfg):
        return {k: (s, jnp.dtype(cfg.dtype))
                for k, (s, _kind) in mod.param_shapes(cfg).items()}

    return {
        "opt-1.3b.serve-chat": (
            tfm, opt, {k: (s, jnp.float32)
                       for k, s in _param_shapes(opt).items()}, 16, 1216 // 16, 16),
        "glm-5.decode-pool-16k": (
            mla_moe, glm, table(mla_moe, glm), 16, 18688 // 16, 16),
        "longcat-flash.decode-pool-12k": (
            scmoe, longcat, table(scmoe, longcat), 16, 15104 // 16, 16),
    }


def _decode_arguments(cell, sharding=None):
    mod, cfg, table, slots, per_slot, page = _served_cells()[cell]

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(tuple(s), dtype, sharding=sharding)

    params = {k: shape(s, dtype) for k, (s, dtype) in table.items()}
    cache = jax.tree.map(
        lambda x: shape(x.shape, x.dtype),
        jax.eval_shape(lambda: mod.init_kv_cache(cfg, slots * per_slot, page)))
    steps = (shape((slots,), jnp.int32), shape((slots, per_slot), jnp.int32),
             shape((slots,), jnp.bool_))
    block_k = getattr(mod, "_decode_block_k", lambda *_: 0)(
        cfg, slots, per_slot * page)
    return mod, cfg, params, cache, steps, (slots, per_slot, page, block_k)


def _as_on_tpu(monkeypatch):
    import mxnet_tpu.kernels  # noqa: F401  (loads kernels.flash_attention)
    from mxnet_tpu.models import mla_moe  # noqa: F401

    for name in ("mxnet_tpu.models.transformer", "mxnet_tpu.models.mla_moe",
                 "mxnet_tpu.kernels.flash_attention"):
        monkeypatch.setattr(sys.modules[name], "kernel_platform", lambda: "tpu")


def decode_digests():
    import hashlib

    out = {}
    with pytest.MonkeyPatch.context() as patch:
        _as_on_tpu(patch)
        for cell in MODULE_DECODE_PROGRAMS:
            mod, cfg, params, cache, (ids, tables, mask), (
                slots, per_slot, page, block_k) = _decode_arguments(cell)
            lowered = jax.jit(mod.make_decode_fn(
                cfg, slots, per_slot, page, block_k=block_k)).trace(
                params, cache, ids, ids, tables, mask).lower(
                lowering_platforms=("tpu",))
            # a Mosaic kernel's serialized body carries the paths of the
            # files it was traced through, this one among them: left out
            text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "", lowered.as_text())
            out[cell] = hashlib.sha256(text.encode()).hexdigest()
    return out


_DECODE_DIGESTS = {}


@pytest.mark.parametrize("cell", sorted(MODULE_DECODE_PROGRAMS))
def test_module_decode_programs_lower_as_before(cell):
    if not _DECODE_DIGESTS:
        _DECODE_DIGESTS.update(decode_digests())
    assert _DECODE_DIGESTS[cell] == MODULE_DECODE_PROGRAMS[cell]


@pytest.mark.parametrize("cell", sorted(MODULE_DECODE_PROGRAMS))
def test_served_decode_chooses_the_token_on_the_device(cell, one_chip,
                                                       monkeypatch):
    from mxnet_tpu.serving import generate

    _as_on_tpu(monkeypatch)
    mod, cfg, params, cache, (ids, tables, mask), (
        slots, per_slot, page, block_k) = _decode_arguments(cell, one_chip)
    counters = len(mod.decode_counters(cfg))
    last = jax.ShapeDtypeStruct((slots + counters,), jnp.int32,
                                sharding=one_chip)
    fn = jax.jit(generate._decode_program(mod, cfg, slots, per_slot, page,
                                          block_k), donate_argnums=(1,))
    compiled = fn.lower(params, cache, last, ids, mask, ids, tables,
                        mask).compile()
    text = compiled.as_text()
    # the trace's name for it, which the benchmark's readers match
    assert re.match(r"HloModule jit_decode[,\s]", text)
    # beside the logits, the ids and the module's counters in one array that
    # the next step takes as it is
    _cache, (logits, chosen) = compiled.out_info
    assert logits.shape == (slots, cfg.vocab)
    assert (chosen.shape, chosen.dtype) == ((slots + counters,), jnp.int32)
    assert (chosen.shape, chosen.dtype) == (last.shape, last.dtype)
    # and the pools are still the donated ones
    pool_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
