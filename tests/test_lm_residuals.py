"""What the LM layer scan saves for the backward (ISSUE 41).

``_layernorm`` and ``_relu`` carry their own VJPs: the LayerNorm keeps its
input as given with a float32 mean and rstd a row, the ReLU its output. The
forwards compute what the plain formulas compute, bit for bit, so the
serving programs (which never differentiate) are unchanged; the gradients
are the plain formulas' autodiff up to float32 summation order.

The scan reads its weight matrices cast to the compute dtype before it
(``_SCAN_CAST``), so it saves no second copy of them and gives their
gradients in that dtype; the values are those of a cast in its body, bit for
bit.
"""
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.spmd import functional_optimizer


def _plain_layernorm(x, gamma, beta, eps=1e-5):
    """The formula before ISSUE 41, differentiated by autodiff."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma + beta).astype(x.dtype)


@pytest.fixture
def plain(monkeypatch):
    """Put the plain formulas back where the model looks them up."""
    def use():
        monkeypatch.setattr(tfm, "_layernorm", _plain_layernorm)
        monkeypatch.setattr(tfm, "_relu", jax.nn.relu)
    return use


def _ln_inputs(dtype, shape=(3, 5, 64), seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.normal(0.3, 2.0, shape), dtype)
    gamma = jnp.asarray(rng.normal(1.0, 0.2, shape[-1:]), jnp.float32)
    beta = jnp.asarray(rng.normal(0.0, 0.2, shape[-1:]), jnp.float32)
    g = jnp.asarray(rng.normal(0.0, 1.0, shape), dtype)
    return x, gamma, beta, g


# -- forward: bit for bit -----------------------------------------------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("jit", [False, True])
def test_layernorm_forward_is_the_plain_formula_bit_for_bit(dtype, jit):
    x, gamma, beta, _ = _ln_inputs(dtype)
    new, old = tfm._layernorm, _plain_layernorm
    if jit:
        new, old = jax.jit(new), jax.jit(old)
    a, b = np.asarray(new(x, gamma, beta)), np.asarray(old(x, gamma, beta))
    assert a.dtype == b.dtype == np.dtype(jnp.dtype(dtype))
    np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))


def test_decode_step_logits_are_the_plain_formulas_bit_for_bit(plain):
    cfg = tfm.TransformerConfig(vocab=96, d_model=64, n_heads=4, n_layers=2,
                                d_ff=128, max_len=64, dtype="bfloat16")
    params = tfm.init_params(cfg, seed=3)
    page, plen = 8, 11
    toks = np.random.RandomState(4).randint(0, cfg.vocab, (1, 16)).astype(np.int32)
    bt = np.zeros((2, 4), np.int32)
    bt[1, :2] = [1, 2]

    def serve():
        cache = tfm.init_kv_cache(cfg, 8, page)
        prefill = jax.jit(tfm.make_prefill_fn(cfg, page))
        decode = jax.jit(tfm.make_decode_fn(cfg, slots=2, max_pages_per_slot=4,
                                            page_size=page, block_k=16))
        cache, first = prefill(params, cache, toks, np.int32(plen),
                               np.array([1, 2], np.int32))
        out = [np.asarray(first)]
        for p in range(plen, plen + 3):
            cache, lg = decode(params, cache, np.array([0, toks[0, p]], np.int32),
                               np.array([0, p], np.int32), bt,
                               np.array([False, True]))
            out.append(np.asarray(lg)[1])
        return out

    new = serve()
    plain()
    old = serve()
    for a, b in zip(new, old):
        np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))


# -- gradients ----------------------------------------------------------------
# float32: the same terms summed in another order, ~1e-7 relative. bfloat16
# input and cotangent: dx is rounded to bfloat16 at the end in both (one ulp,
# 2**-8 of the largest element); dgamma and dbeta are float32 sums of the same
# float32 products in both.
_LN_TOL = {"float32": dict(dx=2e-6, dparam=2e-6),
           "bfloat16": dict(dx=2 ** -8, dparam=2e-6)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_layernorm_vjp_matches_autodiff_of_the_plain_formula(dtype):
    x, gamma, beta, g = _ln_inputs(dtype)
    out, vjp = jax.vjp(tfm._layernorm, x, gamma, beta)
    ref_out, ref_vjp = jax.vjp(_plain_layernorm, x, gamma, beta)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))
    tol = _LN_TOL[dtype]
    for name, a, b in zip(("dx", "dgamma", "dbeta"), vjp(g), ref_vjp(g)):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        bound = tol["dx" if name == "dx" else "dparam"] * np.abs(b).max()
        np.testing.assert_allclose(a, b, rtol=0, atol=bound, err_msg=name)


def test_layernorm_saves_its_input_row_statistics_and_gamma_only():
    from jax._src.ad_checkpoint import saved_residuals

    x, gamma, beta, _ = _ln_inputs("bfloat16", shape=(2, 8, 64))
    res = saved_residuals(tfm._layernorm, x, gamma, beta)
    shapes = sorted((tuple(a.shape), a.dtype.name) for a, _src in res)
    assert shapes == [((2, 8, 1), "float32"), ((2, 8, 1), "float32"),
                      ((2, 8, 64), "bfloat16"), ((64,), "float32")]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_relu_gradient_reads_the_output_and_is_zero_at_zero(dtype):
    x = jnp.asarray([-2.0, -0.0, 0.0, 1e-3, 0.5, 3.0, -1e-3, 0.0], dtype)
    w = jnp.asarray(np.arange(1, 9), dtype)
    grad = jax.grad(lambda v: jnp.sum(tfm._relu(v) * w).astype(jnp.float32))(x)
    ref = jax.grad(lambda v: jnp.sum(jax.nn.relu(v) * w).astype(jnp.float32))(x)
    np.testing.assert_array_equal(np.asarray(tfm._relu(x)), np.asarray(jax.nn.relu(x)))
    np.testing.assert_array_equal(np.asarray(grad), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(grad, np.float32),
                                  [0, 0, 0, 4, 5, 6, 0, 0])
    from jax._src.ad_checkpoint import saved_residuals
    # its one residual is its output (jax.nn.relu's is a bool mask of x)
    res = saved_residuals(tfm._relu, x)
    assert [(a.shape, a.dtype.name) for a, _src in res] == [(x.shape, x.dtype.name)]


# One step of ``make_train_step`` against the same step with the plain
# formulas: the loss bit for bit (the forward is the same), every gradient
# leaf (SGD with momentum 0.9 and rate 1 leaves -g in its state after one
# step) by its norm gap. float32: summation order only. bfloat16: the
# LayerNorm's dx is rounded to bfloat16 in both and the roundings part
# where the float32 values differ in their last bits, then flow back
# through bfloat16 products; over two layers that stays under a percent
# of a leaf's norm (the benchmark's limit on the first gradient is 2 %).
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 1e-2)])
@pytest.mark.parametrize("kw", [{}, {"remat": True}, {"n_experts": 2}],
                         ids=["dense", "remat", "moe"])
def test_train_step_matches_autodiff_of_the_plain_formulas(plain, dtype, tol, kw):
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64, max_len=32, dtype=dtype, **kw)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tokens = jnp.asarray(np.random.RandomState(5).randint(
        0, cfg.vocab, (4, 17)).astype(np.int32))

    def one_step():
        step, place = tfm.make_train_step(
            cfg, mesh, optimizer=functional_optimizer(
                "sgd", learning_rate=1.0, momentum=0.9))
        (_p, state, _n), loss = step(place(tfm.init_params(cfg, seed=6)), tokens)
        return float(loss), {k: -np.asarray(v, np.float64) for k, v in state.items()}

    loss, grads = one_step()
    plain()
    ref_loss, ref_grads = one_step()
    assert loss == ref_loss
    assert grads.keys() == ref_grads.keys()
    for k in grads:
        gap = np.linalg.norm(grads[k] - ref_grads[k])
        assert gap <= tol * max(np.linalg.norm(ref_grads[k]), 1e-12), k


# -- the weights the layer scan reads -------------------------------------------
# ``_forward_local`` casts the matrices of ``_SCAN_CAST`` before the scan. With
# the set empty the body casts them, as it did before: the forward scan then
# stacks each layer's cast again for the backward, and the weight gradients
# come out of the backward scan in float32.
_KINDS = pytest.mark.parametrize("kw", [{}, {"remat": True}, {"n_experts": 2}],
                                 ids=["dense", "remat", "moe"])


def _small(**kw):
    return tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                 d_ff=64, max_len=32, dtype="bfloat16", **kw)


def _layer_scans(cfg):
    """(forward, backward) layer scans in the jaxpr of the loss's gradient,
    and the parameters' shapes."""
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    loss_fn, _ = tfm.make_loss_fn(cfg, mesh)
    params = jax.eval_shape(lambda: tfm.init_params(cfg))
    tokens = jax.ShapeDtypeStruct((4, 17), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(loss_fn))(params, tokens).jaxpr

    def scans(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "scan":
                yield eqn
            for sub in tfm._sub_jaxprs(eqn):
                yield from scans(sub)

    layer = [e for e in scans(jaxpr) if e.params["length"] == cfg.n_layers]
    fwd = [e for e in layer if not e.params["reverse"]]
    bwd = [e for e in layer if e.params["reverse"]]
    assert len(fwd) == len(bwd) == 1
    return fwd[0], bwd[0], params


# what the block reads in float32: the LayerNorms and the router's gate
_READ_IN_FLOAT32 = {"ln1_gamma", "ln1_beta", "ln2_gamma", "ln2_beta", "moe_gate_weight"}


@_KINDS
def test_layer_scan_reads_cast_weights_and_gives_their_gradients_in_cdt(monkeypatch, kw):
    cfg = _small(**kw)
    cdt, f32 = jnp.dtype(cfg.dtype), jnp.dtype(jnp.float32)

    def read():
        fwd, bwd, params = _layer_scans(cfg)
        layer = {k: p.shape for k, p in params.items()
                 if p.shape[:1] == (cfg.n_layers,)}
        matrices = {s for k, s in layer.items() if k not in _READ_IN_FLOAT32}
        xs = fwd.invars[fwd.params["num_consts"] + fwd.params["num_carry"]:]
        saved = sorted(v.aval.shape for v in fwd.outvars if v.aval.shape in matrices)
        grads = {k: [v.aval.dtype for v in bwd.outvars if v.aval.shape == s]
                 for k, s in layer.items()}
        return matrices, {(v.aval.shape, v.aval.dtype) for v in xs}, saved, grads

    matrices, xs, saved, grads = read()
    assert len(matrices) == 4 and ("moe_gate_weight" in grads) == bool(cfg.n_experts)
    # the forward scan reads each matrix stack in the compute dtype as its
    # xs and returns no array shaped like one
    assert xs >= {(s, cdt) for s in matrices}
    assert not saved
    # the backward gives the matrices' gradients in the compute dtype and
    # those of what the block reads in float32 in float32
    for k, dtypes in grads.items():
        want = f32 if k in _READ_IN_FLOAT32 else cdt
        assert dtypes and set(dtypes) == {want}, (k, dtypes)

    # cast in the body: each layer's cast is stacked again (unless the layer
    # is recomputed) and the gradients leave the loop in float32
    monkeypatch.setattr(tfm, "_SCAN_CAST", frozenset())
    _matrices, xs, saved, grads = read()
    assert not xs & {(s, cdt) for s in matrices}
    assert saved == ([] if cfg.remat else sorted(matrices))
    assert all(set(d) == {f32} for d in grads.values())


@_KINDS
def test_cast_before_the_scan_is_the_cast_in_its_body_bit_for_bit(monkeypatch, kw):
    cfg = _small(**kw)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    params = tfm.init_params(cfg, seed=6)
    tokens = jnp.asarray(np.random.RandomState(5).randint(
        0, cfg.vocab, (4, 17)).astype(np.int32))

    def loss_and_grads():
        loss_fn, _ = tfm.make_loss_fn(cfg, mesh)
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, tokens)
        return np.asarray(loss), {k: np.asarray(v) for k, v in grads.items()}

    loss, grads = loss_and_grads()
    monkeypatch.setattr(tfm, "_SCAN_CAST", frozenset())   # cast in the body
    ref_loss, ref_grads = loss_and_grads()
    np.testing.assert_array_equal(loss.view(np.uint32), ref_loss.view(np.uint32))
    assert grads.keys() == ref_grads.keys() == params.keys()
    for k in grads:
        assert grads[k].dtype == ref_grads[k].dtype == np.float32, k
        np.testing.assert_array_equal(grads[k].view(np.uint32),
                                      ref_grads[k].view(np.uint32), err_msg=k)


# -- what the layer scan saves ------------------------------------------------
TINY = dict(vocab=512, d_model=256, n_heads=4, n_layers=2, d_ff=1024,
            max_len=128, dtype="bfloat16")
BATCH = 4     # not the layer count: a residual whose leading dim is 2 is stacked


def _residuals(monkeypatch):
    from jax._src.ad_checkpoint import saved_residuals

    import mxnet_tpu.kernels  # noqa: F401  (loads kernels.flash_attention)
    # the chip's attention: the flash kernel's residuals, traced, never run
    for name in ("mxnet_tpu.models.transformer", "mxnet_tpu.kernels.flash_attention"):
        monkeypatch.setattr(sys.modules[name], "kernel_platform", lambda: "tpu")
    cfg = tfm.TransformerConfig(**TINY)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    loss_fn, _ = tfm.make_loss_fn(cfg, mesh)
    params = jax.eval_shape(lambda: tfm.init_params(cfg))
    tokens = jnp.zeros((BATCH, TINY["max_len"] + 1), jnp.int32)
    return [(tuple(a.shape), a.dtype.name, a.size * a.dtype.itemsize)
            for a, _src in saved_residuals(lambda p: loss_fn(p, tokens), params)]


def _activation_sized(res, dtype):
    B, S = BATCH, TINY["max_len"]
    return [r for r in res if r[1] == dtype and r[0][-3:-1] == (B, S)
            and r[0][-1] in (TINY["d_model"], TINY["d_ff"])]


def test_layer_scan_saves_no_float32_activation_and_no_mask(monkeypatch, plain):
    res = _residuals(monkeypatch)
    L = TINY["n_layers"]
    stacked = [r for r in res if r[0][0] == L]
    assert not _activation_sized(stacked, "float32")
    assert not [r for r in stacked if r[1] == "bool"]
    # outside the scan only the embedding's select keeps an activation-sized
    # float32 and a mask (the final LayerNorm keeps its bfloat16 input)
    outside = [r for r in res if r[0][0] != L]
    assert len(_activation_sized(outside, "float32")) == 1
    assert [r[0] for r in outside if r[1] == "bool"] == [
        (BATCH, TINY["max_len"], TINY["d_model"])]

    plain()
    before = _residuals(monkeypatch)
    assert len(_activation_sized([r for r in before if r[0][0] == L],
                                 "float32")) == 6
    # what the change predicts: a layer saves six float32 (B, S, d) LayerNorm
    # copies and a (B, S, d_ff) mask less, two bfloat16 LayerNorm inputs more;
    # the final LayerNorm four float32 copies less, its bfloat16 input more
    # (at the cell's widths: 5.42 -> 3.66 GB, tools/lm_residuals.py)
    bsd = BATCH * TINY["max_len"] * TINY["d_model"]
    bsf = BATCH * TINY["max_len"] * TINY["d_ff"]
    predicted = L * (6 * 4 * bsd + bsf - 2 * 2 * bsd) + 4 * 4 * bsd - 2 * bsd
    saved = sum(r[2] for r in before) - sum(r[2] for r in res)
    assert saved >= predicted


def test_residual_account_counts_the_cast_weights_apart_from_the_stacks(monkeypatch):
    import os
    monkeypatch.syspath_prepend(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import lm_residuals

    import mxnet_tpu.kernels  # noqa: F401  (loads kernels.flash_attention)
    for name in ("mxnet_tpu.models.transformer", "mxnet_tpu.kernels.flash_attention"):
        monkeypatch.setattr(sys.modules[name], "kernel_platform", lambda: "tpu")
    cfg = tfm.TransformerConfig(**TINY)
    L, d, f, S = cfg.n_layers, cfg.d_model, cfg.d_ff, TINY["max_len"]

    def account():
        res = lm_residuals.residuals(cfg, BATCH, S)
        return res, lm_residuals.summary(res, cfg, BATCH, S)

    res, report = account()
    weights = {(L, d, 3, cfg.n_heads, d // cfg.n_heads), (L, cfg.n_heads, d // cfg.n_heads, d),
               (L, d, f), (L, f, d)}
    assert {r[0] for r in res if r[3] == "scan_xs"} == weights
    assert report["scan_xs_bytes"] == 2 * L * (4 * d * d + 2 * d * f)
    assert not [r for r in res if r[3] == "stacked" and r[0] in weights]

    monkeypatch.setattr(tfm, "_SCAN_CAST", frozenset())   # cast in the body
    _res, before = account()
    assert before["scan_xs_bytes"] == 0
    assert before["stacked_bytes"] - report["stacked_bytes"] == report["scan_xs_bytes"]
    assert before["total_bytes"] == report["total_bytes"]
