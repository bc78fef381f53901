"""Fused-block operators: whole ResNet units as single Pallas-backed ops.

Reference counterpart: none as an *op* — the reference reaches these
fusion boundaries with cuDNN/NNVM graph passes (conv+BN folding is an
inference-only trick there, src/operator/nn/batch_norm.cc keeps training
unfused). On TPU the training-time fusion is the single remaining perf
lever (round-5 record; ROADMAP S1/S2), so the framework exposes it as
a first-class op that the IR fusion pass (``mxnet_tpu/ir/rules.py`` ``bottleneck_fuse``)
emits when rewriting the unfused builder graph (``fused=True`` routes
through that pass since ISSUE 13).

Checkpoint parity: parameter names and OIHW weight shapes match the
unfused builder exactly ("stageX_unitY_conv1_weight",
"stageX_unitY_bn1_gamma", ...), so save/load interoperates with
checkpoints trained either way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register


@register(
    name="FusedBottleneckUnit",
    num_outputs=7,
    num_visible_outputs=1,
    aux_state_outputs={
        "bn1_moving_mean": 1, "bn1_moving_var": 2,
        "bn2_moving_mean": 3, "bn2_moving_var": 4,
        "bn3_moving_mean": 5, "bn3_moving_var": 6,
    },
)
def fused_bottleneck_unit(
    data,
    conv1_weight,
    conv2_weight,
    conv3_weight,
    bn1_gamma,
    bn1_beta,
    bn2_gamma,
    bn2_beta,
    bn3_gamma,
    bn3_beta,
    bn1_moving_mean,
    bn1_moving_var,
    bn2_moving_mean,
    bn2_moving_var,
    bn3_moving_mean,
    bn3_moving_var,
    sc_weight=None,
    num_filter=1,
    stride=1,
    dim_match=True,
    eps=2e-5,
    momentum=0.9,
    __is_train__=False,
):
    """Pre-activation bottleneck unit (BN-ReLU-conv ×3 + shortcut) as one
    fused op in NHWC.

    Equivalent unfused graph: resnet.py residual_unit (bottle_neck=True)
    — same math, same parameter names/shapes (weights OIHW), but the
    normalized activations never touch HBM (kernels/fused_block.py).
    Outputs: (out, new_bn1_mm, new_bn1_mv, ..., new_bn3_mv); the moving
    stats are momentum-mixed in-op and carry no gradient.
    """
    from ..kernels import fused_block as fb

    _register_imperative_post()
    s = int(stride)
    w1 = conv1_weight.transpose(2, 3, 1, 0)  # OIHW -> HWIO
    w2 = conv2_weight.transpose(2, 3, 1, 0)
    w3 = conv3_weight.transpose(2, 3, 1, 0)
    wsc = None if sc_weight is None else sc_weight.transpose(2, 3, 1, 0)
    moving = (bn1_moving_mean, bn1_moving_var, bn2_moving_mean,
              bn2_moving_var, bn3_moving_mean, bn3_moving_var)
    # Under a TrainStep mesh the Pallas kernels must be partitioned
    # explicitly (shard_map over the data axes) — Mosaic kernels are
    # opaque to pjit's partitioner on real TPU (fused_block.py spmd
    # wrappers; set by parallel/spmd.py at trace time).
    scope = fb.current_spmd_scope()
    if __is_train__:
        if scope is not None:
            mesh, axes = scope
            out, stats = fb.bottleneck_train_spmd(
                data, w1, w2, w3, wsc, bn1_gamma, bn1_beta, bn2_gamma,
                bn2_beta, bn3_gamma, bn3_beta, s, float(eps), None,
                mesh, axes)
        else:
            out, stats = fb.bottleneck_train(
                data, w1, w2, w3, wsc, bn1_gamma, bn1_beta, bn2_gamma,
                bn2_beta, bn3_gamma, bn3_beta, s, float(eps), None)
        m = float(momentum)
        new = tuple(
            (m * old.astype(jnp.float32)
             + (1.0 - m) * jax.lax.stop_gradient(st)).astype(old.dtype)
            for old, st in zip(moving, stats))
        return (out,) + new
    if scope is not None:
        mesh, axes = scope
        out = fb.bottleneck_infer_spmd(
            data, w1, w2, w3, wsc, bn1_gamma, bn1_beta, bn2_gamma, bn2_beta,
            bn3_gamma, bn3_beta, *moving, stride=s, eps=float(eps),
            mesh=mesh, axes=axes)
    else:
        out = fb.bottleneck_infer(
            data, w1, w2, w3, wsc, bn1_gamma, bn1_beta, bn2_gamma, bn2_beta,
            bn3_gamma, bn3_beta, *moving, stride=s, eps=float(eps))
    return (out,) + moving


@register(name="_ConvResidualAdd")
def _conv_residual_add(
    data,
    weight,
    residual,
    bias=None,
    kernel=(),
    stride=(),
    dilate=(),
    pad=(),
    num_filter=1,
    num_group=1,
    workspace=1024,
    no_bias=False,
    layout=None,
):
    """Convolution with the residual add fused into its epilogue.

    Emitted by the ``residual_conv_epilogue`` IR rule
    (``mxnet_tpu/ir/rules.py``): ``Convolution(x, w[, b]) + residual``
    becomes one op, so the add rides the convolution's epilogue (XLA
    fuses the elementwise tail into the conv consumer; the Pallas
    conv-family schedule applies — the rule names ``fused_fwd`` in the
    autotuner's sweepable set). Same math as the unfused pair, exactly.
    """
    from .nn import convolution

    out = convolution(data, weight, bias, kernel=kernel, stride=stride,
                      dilate=dilate, pad=pad, num_filter=num_filter,
                      num_group=num_group, workspace=workspace,
                      no_bias=no_bias, layout=layout)
    return out + residual


_POST_REGISTERED = False


def _register_imperative_post():
    """Moving-stat rebind for the imperative path (the executor path uses
    the generic aux_state_outputs contract instead). Registered lazily on
    first op application — ndarray imports the ops package, so a
    module-level registration would be a circular import."""
    global _POST_REGISTERED
    if _POST_REGISTERED:
        return
    from ..ndarray.ndarray import register_stateful_post

    @register_stateful_post("FusedBottleneckUnit")
    def _fused_unit_post(inputs, results, attrs):
        if not attrs.get("__is_train__"):
            return
        for out_idx, in_idx in ((1, 10), (2, 11), (3, 12), (4, 13),
                                (5, 14), (6, 15)):
            t = inputs[in_idx] if in_idx < len(inputs) else None
            if t is not None and hasattr(t, "_rebind"):
                t._rebind(results[out_idx])

    _POST_REGISTERED = True
