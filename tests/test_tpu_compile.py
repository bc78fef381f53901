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
