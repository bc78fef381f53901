"""Fused Pallas bottleneck block (kernels/fused_block.py + the
FusedBottleneckUnit op): kernel-level parity with an unfused jnp graph,
and model-level parity of the fused ResNet builder against the unfused
symbolic graph — both run in interpret mode on CPU (the same code path
compiles on TPU).

Reference bar: the fused unit must be a drop-in for residual_unit in
example/image-classification/symbols/resnet.py (same math, same
parameter names, same OIHW checkpoint shapes)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu.kernels import fused_block as fb

EPS = 2e-5


def _ref_bn_relu(x, g, b, eps=EPS):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, (0, 1, 2))
    var = jnp.maximum(jnp.mean(xf * xf, (0, 1, 2)) - mean * mean, 0.0)
    inv = lax.rsqrt(var + eps)
    return jnp.maximum((xf - mean) * inv * g + b, 0.0).astype(x.dtype)


def _ref_conv(x, w, stride):
    pad = w.shape[0] // 2
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC")).astype(x.dtype)


def _ref_unit(data, w1, w2, w3, wsc, g1, b1, g2, b2, g3, b3, stride):
    a1 = _ref_bn_relu(data, g1, b1)
    y1 = _ref_conv(a1, w1, 1)
    a2 = _ref_bn_relu(y1, g2, b2)
    y2 = _ref_conv(a2, w2, stride)
    a3 = _ref_bn_relu(y2, g3, b3)
    y3 = _ref_conv(a3, w3, 1)
    sc = data if wsc is None else _ref_conv(a1, wsc, stride)
    return y3 + sc


def _case(stride, dim_match, seed=0, n=2, h=8, w=8, ci=8, c=8):
    co = ci if dim_match else 16
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))  # noqa: E731
    return (f(n, h, w, ci), f(1, 1, ci, c), f(3, 3, c, c), f(1, 1, c, co),
            None if dim_match else f(1, 1, ci, co),
            f(ci) + 1.0, f(ci) * 0.1, f(c) + 1.0, f(c) * 0.1,
            f(c) + 1.0, f(c) * 0.1)


@pytest.mark.parametrize("stride,dim_match", [(1, True), (1, False),
                                              (2, False)])
def test_fused_unit_forward_and_grads(stride, dim_match):
    args = _case(stride, dim_match)
    out_f, stats = fb.bottleneck_train(*args, stride, EPS, True)
    out_r = _ref_unit(*args, stride)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                               atol=2e-4)
    assert all(np.all(np.isfinite(np.asarray(s))) for s in stats)

    cot = jnp.asarray(np.random.RandomState(9).randn(*out_r.shape)
                      .astype(np.float32))
    idxs = [i for i in range(11) if args[i] is not None]
    gf = jax.grad(lambda *a: jnp.sum(
        fb.bottleneck_train(*a, stride, EPS, True)[0] * cot),
        argnums=idxs)(*args)
    gr = jax.grad(lambda *a: jnp.sum(_ref_unit(*a, stride) * cot),
                  argnums=idxs)(*args)
    for a, b in zip(gf, gr):
        scale = float(jnp.max(jnp.abs(b))) + 1e-6
        assert float(jnp.max(jnp.abs(a - b))) / scale < 5e-4


def test_fused_unit_multi_tile_halos():
    """Force 2-row tiles so halo rows cross tile boundaries."""
    orig = fb._tile_rows
    fb._tile_rows = lambda h: 2 if h % 2 == 0 else 1
    try:
        for stride, dm in [(1, True), (2, False)]:
            args = _case(stride, dm, seed=3)
            out_f, _ = fb.bottleneck_train(*args, stride, EPS, True)
            np.testing.assert_allclose(np.asarray(out_f),
                                       np.asarray(_ref_unit(*args, stride)),
                                       atol=2e-4)
    finally:
        fb._tile_rows = orig


def _tiny_resnet(fused, num_classes=5):
    from mxnet_tpu.models.resnet import resnet

    return resnet(units=[2, 1], num_stages=2, filter_list=[8, 16, 32],
                  num_classes=num_classes, image_shape=(3, 64, 64),
                  bottle_neck=True, fused=fused)


def test_fused_resnet_matches_unfused():
    """The fused builder is numerically the same network: identical
    params (names AND shapes), matching train-mode forward + backward
    and inference forward."""
    sf = _tiny_resnet(True)
    su = _tiny_resnet(False)
    shapes = dict(data=(2, 3, 64, 64), softmax_label=(2,))
    af, _, auxf = sf.infer_shape(**shapes)
    au, _, auxu = su.infer_shape(**shapes)
    args_f = dict(zip(sf.list_arguments(), af))
    args_u = dict(zip(su.list_arguments(), au))
    assert args_f == args_u
    assert dict(zip(sf.list_auxiliary_states(), auxf)) == \
        dict(zip(su.list_auxiliary_states(), auxu))

    rng = np.random.RandomState(0)
    vals = {k: mx.nd.array(rng.randn(*v).astype(np.float32) * 0.1)
            for k, v in args_f.items()}
    for k in vals:
        if k.endswith("_gamma"):
            vals[k] = mx.nd.array(np.ones(args_f[k], np.float32))
    data = rng.randn(2, 3, 64, 64).astype(np.float32)
    label = rng.randint(0, 5, (2,)).astype(np.float32)
    vals["data"] = mx.nd.array(data)
    vals["softmax_label"] = mx.nd.array(label)

    outs = {}
    grads = {}
    for name, s in (("fused", sf), ("unfused", su)):
        ex = s.simple_bind(mx.cpu(), grad_req="write", **shapes)
        ex.copy_params_from(
            {k: v for k, v in vals.items() if k in args_f},
            dict(zip(s.list_auxiliary_states(),
                     [mx.nd.zeros(v) if "mean" in n else mx.nd.ones(v)
                      for n, v in zip(s.list_auxiliary_states(),
                                      auxf if name == "fused" else auxu)])))
        out = ex.forward(is_train=True, data=vals["data"],
                         softmax_label=vals["softmax_label"])[0]
        ex.backward()
        outs[name] = out.asnumpy()
        grads[name] = {k: g.asnumpy() for k, g in
                       zip(s.list_arguments(), ex.grad_arrays)
                       if g is not None}

    np.testing.assert_allclose(outs["fused"], outs["unfused"], atol=2e-4)
    for k in grads["unfused"]:
        if k in ("data", "softmax_label"):
            continue
        a, b = grads["fused"][k], grads["unfused"][k]
        # 2e-3 of the gradient's own scale, over a 1e-6 floor: every
        # gradient here differs by 1e-8..2e-6 absolute, the fp32 rounding
        # of the O(0.1) terms summed into it. bn0_gamma's terms cancel
        # to 7e-5 (the next layer normalizes its scale away), so its
        # 1.6e-7 is 2.2e-3 of its own max and says nothing about the
        # kernel; the next worst is bn0_beta at 2.4e-4, the rest < 2e-5.
        assert np.abs(a - b).max() < 2e-3 * np.abs(b).max() + 1e-6, k


def test_fused_resnet_trains_and_infers():
    """End-to-end: Module.fit on the fused graph learns a separable
    task, aux moving stats move, and score() (inference mode, moving
    stats) agrees with training accuracy direction."""
    mx.random.seed(5)  # pin initializer draws (deterministic training)
    rng = np.random.RandomState(0)
    n = 32
    x = rng.randn(n, 3, 64, 64).astype(np.float32)
    y = (rng.rand(n) > 0.5).astype(np.float32)
    x[y == 1, :, 8:24, 8:24] += 2.0

    sf = _tiny_resnet(True, num_classes=2)
    it = mx.io.NDArrayIter(x, y, 8, label_name="softmax_label")
    mod = mx.mod.Module(sf, context=mx.cpu())
    mod.fit(it, num_epoch=3, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.initializer.Xavier(), eval_metric="acc")
    _, aux = mod.get_params()
    moved = [k for k, v in aux.items()
             if "moving_mean" in k and np.abs(v.asnumpy()).max() > 1e-6]
    assert moved, "fused unit moving stats never updated"
    it.reset()
    acc = mod.score(it, "acc")[0][1]
    assert acc > 0.7, acc
