"""TrainStep rematerialization: off, full (``True``) or ``"conv"``.

The three modes change what the backward pass keeps, never the math:
each is one ``jax.checkpoint`` policy around the loss closure. The
graph under test is the one the benchmark's fit cell compiles, cut to
size: a two-unit bottleneck ResNet from ``models/resnet.py``.

Measurement discipline: what a mode saves is read from the AD-level
backward-residual set (``TrainStep.residual_stats``, built on jax's
``saved_residuals``) — NOT ``memory_analysis()`` temp bytes, because
XLA's CPU pipeline strips the checkpoint's optimization barriers and
CSE-merges the recompute back into the forward, so compiled temp bytes
on CPU cannot show what the TPU compiler (which honors the barriers)
does. The residual set is the thing the policy controls on every
backend.
"""
import functools

import numpy as np
import pytest

from mxnet_tpu import config, symbol as sym
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.resnet import resnet
from mxnet_tpu.parallel.spmd import TrainStep, functional_optimizer


def _sgd():
    return functional_optimizer("sgd", learning_rate=0.1)


def _tiny_resnet():
    """One stage of two bottleneck units (projection + identity
    shortcut) behind the cifar stem: every op kind ResNet-50 has."""
    return resnet(units=[2], num_stages=1, filter_list=[8, 16],
                  num_classes=4, image_shape=(3, 8, 8), bottle_neck=True)


def _fc_ln():
    """FullyConnected + LayerNorm blocks with a residual add: no
    BatchNorm, so XLA fuses the recomputation as it fuses the forward
    and the three modes agree to the bit."""
    body = sym.Variable("data")
    for i in range(2):
        h = sym.LayerNorm(body, name="ln%d" % i)
        h = sym.FullyConnected(h, num_hidden=32, name="up%d" % i)
        h = sym.Activation(h, act_type="relu", name="act%d" % i)
        h = sym.FullyConnected(h, num_hidden=16, name="down%d" % i)
        body = body + h
    fc = sym.FullyConnected(body, num_hidden=4, name="head")
    return sym.SoftmaxOutput(fc, name="softmax")


def _batch(shape, seed=0):
    rng = np.random.RandomState(seed)
    return {"data": rng.randn(*shape).astype(np.float32),
            "softmax_label": rng.randint(0, 4, shape[:1])
            .astype(np.float32)}


GRAPHS = {
    # name -> (builder, data shape, bit-identical across modes)
    "fc_ln": (_fc_ln, (4, 16), True),
    "resnet": (_tiny_resnet, (4, 3, 8, 8), False),
}


def _train(ts, batch, steps=3):
    import jax

    shapes = {k: tuple(v.shape) for k, v in batch.items()}
    params, opt_state, aux = ts.init_params(shapes, seed=0)
    carry = ts.place(params, opt_state, aux)
    key = jax.random.PRNGKey(0)
    losses = []
    for _ in range(steps):
        carry, loss = ts(carry, batch, key)
        losses.append(float(loss))
    return carry, losses


@functools.lru_cache(maxsize=None)
def _ref_run(graph, compute_dtype):
    """The explicit remat-off run every mode is compared with (one
    compile per graph and dtype, shared by the tests of this file)."""
    build, shape, _ = GRAPHS[graph]
    s, batch = build(), _batch(shape)
    return (s, batch) + _train(
        TrainStep(s, _sgd(), remat=False, compute_dtype=compute_dtype),
        batch)


def _assert_run_matches(ref, carry, losses, exact, tag):
    _, _, ref_carry, ref_losses = ref
    if exact:
        assert losses == ref_losses, tag
    else:
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-5,
                                   err_msg=tag)
    for part in (0, 2):  # params, aux
        for k in ref_carry[part]:
            a, b = np.asarray(ref_carry[part][k]), np.asarray(carry[part][k])
            if exact:
                np.testing.assert_array_equal(a, b, err_msg="%s/%s" % (tag, k))
            else:
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                           err_msg="%s/%s" % (tag, k))


# ---------------------------------------------------------------------------
# what each mode saves
# ---------------------------------------------------------------------------
def test_remat_trains_within_memory_budget():
    """The OOM framing, made analytic (CPU has no HBM ceiling): on the
    bottleneck ResNet the residual set shrinks off > "conv" > full, as
    examples/memcost reports it for the compiled bytes, so a budget
    that remat off busts is one "conv" fits. (That both modes train is
    the parametrised test below, which runs real steps.)"""
    s, batch = _tiny_resnet(), _batch(GRAPHS["resnet"][1])
    shapes = {k: tuple(v.shape) for k, v in batch.items()}
    params, _, aux = TrainStep(s, _sgd()).init_params(shapes, seed=0)
    res = {mode: TrainStep(s, _sgd(), remat=mode)
           .residual_stats(params, aux, batch)
           for mode in (False, "conv", True)}
    off, conv, full = (res[m]["residual_bytes"] for m in (False, "conv", True))
    assert full < conv < off, res
    assert res["conv"]["n_residuals"] < res[False]["n_residuals"]
    budget = (off + conv) // 2
    assert conv <= budget < off


# ---------------------------------------------------------------------------
# the modes agree; the default is remat off on the caller's graph
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", [True, "conv"], ids=["full", "conv"])
@pytest.mark.parametrize("graph,compute_dtype", [
    ("fc_ln", None), ("fc_ln", "bfloat16"), ("resnet", None)])
def test_remat_conv_and_full_train_as_remat_off(graph, compute_dtype, mode):
    """Three steps under full and "conv" remat end on the parameters,
    moving statistics and losses of remat off: bit-identical on the
    FullyConnected/LayerNorm graph, in float32 and under
    ``compute_dtype="bfloat16"`` (the cast sits inside the checkpointed
    closure, so the recomputation rounds where the forward did); on
    the ResNet within float32 rounding, because XLA fuses the
    recomputed BatchNorm statistics into other reductions than the
    forward's, which reorders their sums. The ResNet under bfloat16 is
    no case: with XLA's ``xla_allow_excess_precision`` (on by default)
    a fusion keeps float32 where it can, "conv" fuses otherwise than
    off, and the two then differ by bfloat16 roundings that BatchNorm
    over 4 x 8 x 8 values amplifies to 4 % of a weight in one step;
    with that flag off they agree to the bit."""
    ref = _ref_run(graph, compute_dtype)
    carry, losses = _train(
        TrainStep(ref[0], _sgd(), remat=mode, compute_dtype=compute_dtype),
        ref[1])
    _assert_run_matches(ref, carry, losses, GRAPHS[graph][2],
                        "%s/%s/%s" % (graph, mode, compute_dtype))


def test_default_train_step_is_remat_off_on_the_callers_symbol(monkeypatch):
    """TrainStep compiles the graph it is given: built with no remat
    argument and no knob set, it holds the caller's symbol object,
    ``remat is False``, and trains as the explicit off run does."""
    monkeypatch.delenv("MXNET_TPU_REMAT", raising=False)
    ref = _ref_run("resnet", None)
    ts_default = TrainStep(ref[0], _sgd())
    assert ts_default.symbol is ref[0]
    assert ts_default.remat is False
    carry, losses = _train(ts_default, ref[1])
    _assert_run_matches(ref, carry, losses, True, "default")


# ---------------------------------------------------------------------------
# bugfix regression: remat="conv" must cover the fused-unit prims
# ---------------------------------------------------------------------------
def _fused_symbol():
    data = sym.Variable("data")
    body = sym.transpose(data, axes=(0, 2, 3, 1), name="to_nhwc")
    body = sym.FusedBottleneckUnit(body, num_filter=8, stride=1,
                                   dim_match=False, eps=2e-5,
                                   momentum=0.9, name="unit1")
    body = sym.transpose(body, axes=(0, 3, 1, 2), name="to_nchw")
    body = sym.Pooling(body, global_pool=True, kernel=(4, 4),
                       pool_type="avg", name="pool")
    fc = sym.FullyConnected(sym.Flatten(body), num_hidden=4, name="fc")
    return sym.SoftmaxOutput(fc, name="softmax")


def test_remat_conv_policy_covers_fused_unit_prims(monkeypatch):
    """Regression for the satellite bugfix: the conv policy's prim set
    once held only conv_general_dilated/dot_general, so a fused-
    bottleneck graph (traced as custom_vjp/pallas prims) silently
    recomputed its MXU work. Now _SAVEABLE_PRIMS covers the fused
    prims: the traced prim name is in the set, and the saved-residual
    footprint shrinks to the old policy when the fix is reverted."""
    import jax

    from mxnet_tpu.parallel import spmd

    s = _fused_symbol()
    rng = np.random.RandomState(0)
    batch = {"data": rng.randn(2, 8, 8, 8).astype(np.float32),
             "softmax_label": rng.randint(0, 4, (2,))
             .astype(np.float32)}
    shapes = {k: tuple(v.shape) for k, v in batch.items()}
    ts = TrainStep(s, _sgd(), remat="conv")
    params, _, aux = ts.init_params(shapes, seed=0)

    # the fused unit's traced prim is actually in the policy set
    plain = TrainStep(s, _sgd(), remat=False)._loss_closure()
    jaxpr = jax.make_jaxpr(
        lambda p: plain(p, aux, batch, jax.random.PRNGKey(0)))(params)
    names = set()

    def walk(j):
        for eqn in j.eqns:
            names.add(eqn.primitive.name)
            for v in eqn.params.values():
                if hasattr(v, "eqns"):
                    walk(v)
                elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                    walk(v.jaxpr)

    walk(jaxpr.jaxpr)
    fused_prims = names & {"custom_vjp_call", "custom_vjp_call_jaxpr",
                           "custom_jvp_call", "custom_jvp_call_jaxpr",
                           "pallas_call"}
    assert fused_prims, sorted(names)
    assert fused_prims <= set(spmd._SAVEABLE_PRIMS)

    # behavioral: reverting the fix (the pre-ISSUE-19 prim set) drops
    # the fused unit's outputs from the residual set
    fixed = ts.residual_stats(params, aux, batch)
    monkeypatch.setattr(spmd, "_SAVEABLE_PRIMS",
                        ("conv_general_dilated", "dot_general"))
    reverted = ts.residual_stats(params, aux, batch)
    assert fixed["residual_bytes"] > reverted["residual_bytes"]


# ---------------------------------------------------------------------------
# the option and its knob take three values
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("word", ["pass", "bogus"])
@pytest.mark.parametrize("via", ["arg", "env"])
def test_remat_rejects_anything_but_off_full_conv(via, word, monkeypatch):
    """``"pass"`` (the per-node plan, removed in PR 32) is rejected as
    any other word is, and the error names the three values."""
    s = _fc_ln()
    if via == "env":
        monkeypatch.setenv("MXNET_TPU_REMAT", word)
        with pytest.raises(MXNetError, match="MXNET_TPU_REMAT"):
            TrainStep(s, _sgd())
    else:
        monkeypatch.delenv("MXNET_TPU_REMAT", raising=False)
        with pytest.raises(MXNetError, match=r"False\|True\|'conv'"):
            TrainStep(s, _sgd(), remat=word)


def test_remat_knob_values(monkeypatch):
    s = _fc_ln()
    for raw, want in (("0", False), ("off", False), ("1", True),
                      ("conv", "conv")):
        monkeypatch.setenv("MXNET_TPU_REMAT", raw)
        got = TrainStep(s, _sgd()).remat
        assert got == want and type(got) is type(want), raw
    # the explicit argument wins over the knob
    assert TrainStep(s, _sgd(), remat=False).remat is False


def test_train_step_takes_no_pass_pipeline(monkeypatch):
    """The graph a TrainStep compiles is the caller's to rewrite
    (``ir.apply_passes``) before it is handed over: the option is a
    TypeError and its knobs are not in the registry."""
    with pytest.raises(TypeError):
        TrainStep(_fc_ln(), _sgd(), train_passes=("fusion",))
    assert "MXNET_IR_TRAIN_PASSES" not in config.KNOBS
    assert "MXNET_IR_LAYOUT" not in config.KNOBS
