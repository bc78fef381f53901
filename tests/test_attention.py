"""Ring/Ulysses sequence parallelism + Pallas flash attention tests.

Model: SURVEY §4 test strategy — N CPU-backed jax devices stand in for the
TPU mesh; Pallas kernels run in interpreter mode off-TPU.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.ring import (
    full_attention, ring_attention, ulysses_attention,
)
from mxnet_tpu.kernels import flash_attention


def _qkv(B=2, H=4, S=64, D=16, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(
        jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
        for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    q, k, v = _qkv()
    mesh = make_mesh({"sp": 8})
    ref = full_attention(q, k, v, causal=causal)
    out = ring_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(causal):
    q, k, v = _qkv(H=8)
    mesh = make_mesh({"sp": 8})
    ref = full_attention(q, k, v, causal=causal)
    out = ulysses_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_composes_with_dp_and_grads():
    q, k, v = _qkv()
    mesh = make_mesh({"dp": 2, "sp": 4})

    def loss(q):
        return ring_attention(q, k, v, mesh, causal=True,
                              batch_axis="dp").sum()

    def loss_ref(q):
        return full_attention(q, k, v, causal=True).sum()

    g = jax.grad(loss)(q)
    gr = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_forward(causal):
    q, k, v = _qkv(S=256, D=64)
    ref = full_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads(causal):
    q, k, v = _qkv(B=1, H=2, S=128, D=32)

    g = jax.grad(lambda *a: flash_attention(*a, causal=causal).sum(),
                 argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: full_attention(*a, causal=causal).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_flash_attention_uneven_q_and_bf16():
    q, k, v = _qkv(S=256, D=64)
    out = flash_attention(q[:, :, :200], k, v, block_q=128)
    ref = full_attention(q[:, :, :200], k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, causal=True)
    ref = full_attention(qb, kb, vb, causal=True)
    assert np.abs(np.asarray(out.astype(jnp.float32))
                  - np.asarray(ref.astype(jnp.float32))).max() < 0.05


def _flash_module():
    """The kernels' module (``mxnet_tpu.kernels`` exports a function of the
    same name over it)."""
    import sys

    import mxnet_tpu.kernels  # noqa: F401
    return sys.modules["mxnet_tpu.kernels.flash_attention"]


def _flash_and_grads(attend, q, k, v, do):
    out, vjp = jax.vjp(attend, q, k, v)
    return (out,) + vjp(do.astype(out.dtype))


# bfloat16 keeps 8 bits: one rounding moves a number by at most 2**-8 of
# itself, and out / p / ds are each rounded once on top of the inputs'
_BF16_TOL = 2 * float(jnp.finfo(jnp.bfloat16).eps)


@pytest.mark.parametrize("d", [32, 64])
@pytest.mark.parametrize("seq", [(1024, 1024), (512, 512), (200, 256),
                                 (32, 32)],
                         ids=["several_blocks", "512", "uneven", "one_block"])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_bf16_matches_float32_reference(causal, seq, d):
    """bfloat16 operands into every product, float32 statistics: forward
    and the three gradients against ``full_attention`` in float32 on the
    same rounded inputs, under the blocks derived from the shape."""
    sq, sk = seq
    rng = np.random.RandomState(sq + d)
    q, k, v, do = (
        jnp.asarray(rng.randn(1, 2, s, d).astype(np.float32)
                    ).astype(jnp.bfloat16)
        for s in (sq, sk, sk, sq))
    got = _flash_and_grads(
        lambda *a: flash_attention(*a, causal=causal), q, k, v, do)
    want = _flash_and_grads(
        lambda *a: full_attention(*a, causal=causal),
        *(x.astype(jnp.float32) for x in (q, k, v)), do)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        assert g.dtype == jnp.bfloat16 and g.shape == w.shape, name
        w = np.asarray(w)
        err = np.abs(np.asarray(g.astype(jnp.float32)) - w).max()
        assert err <= _BF16_TOL * np.abs(w).max(), (name, err)


@pytest.mark.parametrize("blocks", [(32, 16), (16, 32), (32, 32)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_masks_across_small_blocks(causal, blocks):
    """100 queries and keys in blocks of 16 / 32: blocks below the diagonal,
    on it and skipped above it, and a last key block that holds padding
    (``kv_len`` < ``seq_k``). Forward and the three gradients."""
    q, k, v = _qkv(B=1, H=2, S=100, D=16)
    do = _qkv(B=1, H=2, S=100, D=16, seed=1)[0]
    got = _flash_and_grads(
        lambda *a: flash_attention(*a, causal=causal, block_q=blocks[0],
                                   block_k=blocks[1]), q, k, v, do)
    want = _flash_and_grads(lambda *a: full_attention(*a, causal=causal),
                            q, k, v, do)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_key_blocks_skip_only_hidden_blocks(causal):
    """``_key_blocks`` against the mask itself: every block it leaves out
    is wholly hidden, and under the causal mask the last one it visits is
    not."""
    fa = _flash_module()
    seq_q, seq_k = 96, 128
    rows = np.arange(seq_q)[:, None]
    cols = np.arange(seq_k)[None, :]
    ok = np.broadcast_to((rows >= cols) if causal else True, (seq_q, seq_k))
    for bq, bk in [(16, 32), (32, 16), (32, 32), (48, 64)]:
        for iq in range(seq_q // bq):
            n_kb = int(fa._key_blocks(iq, bq, bk, seq_k, causal))
            assert 1 <= n_kb <= seq_k // bk
            visible = ok[iq * bq:(iq + 1) * bq]
            assert not visible[:, n_kb * bk:].any()
            assert visible[:, (n_kb - 1) * bk:n_kb * bk].any()


@pytest.mark.parametrize("seq, block, padded", [
    (1, 1, 1), (32, 32, 32), (200, 200, 200), (512, 512, 512),
    (600, 128, 640), (1024, 512, 1024), (1536, 512, 1536),
    (1280, 256, 1280), (2048, 512, 2048), (2050, 128, 2176)])
def test_flash_derived_block_tiles_the_padded_sequence(seq, block, padded):
    assert _flash_module()._derived_block(seq) == block
    assert -(-seq // block) * block == padded and padded - seq < 128
