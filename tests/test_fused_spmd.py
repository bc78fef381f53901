"""shard_map partitioning of the fused Pallas bottleneck (VERDICT r4 #2).

pjit can partition the *interpret-mode* fused graph freely (it is plain
jax ops under interpret), but real Mosaic kernels are opaque to the
partitioner — so the fused train step must place its Pallas calls inside
``shard_map`` with explicit psums (kernels/fused_block.py spmd
wrappers). These tests pin:

- kernel-level parity: ``bottleneck_train_spmd`` on an 8-device mesh ==
  ``bottleneck_train`` single-device (fwd, stats, all 11 grads);
- step-level parity: the fused-ResNet TrainStep on a dp mesh matches
  the no-mesh step (outputs + params after one update);
- the two-axis ("dcn","dp") global-mesh layout compiles and matches —
  the multi-host fused path's sharding shape;
- init_params determinism: same seed => same params (the initializer
  zoo draws from random.initializer_rng, which init_params must seed).

Reference bar for the reduction semantics this replaces:
src/kvstore/comm.h:484-690 (device-tree reduce) — here the weight-grad
and BN-stat all-reduces are explicit psums riding ICI inside the step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import mxnet_tpu as mx
from mxnet_tpu.kernels import fused_block as fb
from mxnet_tpu.models import resnet
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.spmd import TrainStep, data_sharding, functional_optimizer


def _mesh(n=8, names=("dp",), shape=None):
    devs = np.array(jax.devices()[:n])
    if shape is not None:
        devs = devs.reshape(shape)
    return Mesh(devs, names)


@pytest.mark.slow   # PR 21: tier-1 wall (tests/README.md)
@pytest.mark.parametrize("stride,shortcut", [(1, False), (2, True)])
def test_bottleneck_spmd_matches_single_device(stride, shortcut):
    n, h, w, ci, csq = 8, 8, 8, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    data = jax.random.normal(ks[0], (n, h, w, ci), jnp.float32)
    w1 = jax.random.normal(ks[1], (1, 1, ci, csq)) * 0.2
    w2 = jax.random.normal(ks[2], (3, 3, csq, csq)) * 0.2
    w3 = jax.random.normal(ks[3], (1, 1, csq, ci)) * 0.2
    wsc = (jax.random.normal(ks[4], (1, 1, ci, ci)) * 0.2) if shortcut else None
    gs = [jnp.ones((c,)) for c in (ci, csq, csq)]
    bs = [jnp.zeros((c,)) for c in (ci, csq, csq)]
    mesh = _mesh(4)

    def loss_spmd(d, a1, a2, a3, asc):
        out, stats = fb.bottleneck_train_spmd(
            d, a1, a2, a3, asc, gs[0], bs[0], gs[1], bs[1], gs[2], bs[2],
            stride, 1e-5, None, mesh, ("dp",))
        return jnp.sum(out ** 2) * 1e-3, stats

    def loss_ref(d, a1, a2, a3, asc):
        out, stats = fb.bottleneck_train(
            d, a1, a2, a3, asc, gs[0], bs[0], gs[1], bs[1], gs[2], bs[2],
            stride, 1e-5, None)
        return jnp.sum(out ** 2) * 1e-3, stats

    (v1, st1), gr1 = jax.jit(jax.value_and_grad(
        loss_spmd, argnums=(0, 1, 2, 3, 4), has_aux=True))(data, w1, w2, w3, wsc)
    (v2, st2), gr2 = jax.jit(jax.value_and_grad(
        loss_ref, argnums=(0, 1, 2, 3, 4), has_aux=True))(data, w1, w2, w3, wsc)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(st1), jax.tree.leaves(st2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)
    for a, b in zip(jax.tree.leaves(gr1), jax.tree.leaves(gr2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)


@pytest.mark.slow   # PR 21: tier-1 wall (tests/README.md)
def test_bottleneck_infer_spmd_matches_single_device():
    n, h, w, ci, csq = 8, 8, 8, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 12)
    data = jax.random.normal(ks[0], (n, h, w, ci), jnp.float32)
    w1 = jax.random.normal(ks[1], (1, 1, ci, csq)) * 0.2
    w2 = jax.random.normal(ks[2], (3, 3, csq, csq)) * 0.2
    w3 = jax.random.normal(ks[3], (1, 1, csq, ci)) * 0.2
    gs = [jnp.ones((c,)) for c in (ci, csq, csq)]
    bs = [jnp.zeros((c,)) for c in (ci, csq, csq)]
    mm = [jax.random.normal(ks[4 + i], (c,)) * 0.1
          for i, c in enumerate((ci, csq, csq))]
    mv = [jnp.abs(jax.random.normal(ks[8 + i], (c,))) + 0.5
          for i, c in enumerate((ci, csq, csq))]
    mesh = _mesh(4)
    args = (data, w1, w2, w3, None, gs[0], bs[0], gs[1], bs[1], gs[2], bs[2],
            mm[0], mv[0], mm[1], mv[1], mm[2], mv[2])
    out_s = fb.bottleneck_infer_spmd(*args, stride=1, eps=1e-5,
                                     mesh=mesh, axes=("dp",))
    out_r = fb.bottleneck_infer(*args, stride=1, eps=1e-5)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_r),
                               rtol=1e-5, atol=1e-6)


def _fused_sym():
    return resnet.resnet(units=[1, 1], num_stages=2, filter_list=[8, 16, 32],
                         num_classes=16, image_shape=(3, 32, 32),
                         bottle_neck=True, fused=True)


def _run_steps(ts, pn, an, batch_np, n_steps=2, place_sharding=None):
    p = {k: jnp.asarray(v) for k, v in pn.items()}
    a = {k: jnp.asarray(v) for k, v in an.items()}
    carry = ts.place(p, ts.optimizer.init(p), a)
    if place_sharding is not None:
        batch = {k: jax.device_put(v, place_sharding)
                 for k, v in batch_np.items()}
    else:
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
    losses, outs = [], None
    for i in range(n_steps):
        carry, (loss, outs) = ts(carry, batch, jax.random.PRNGKey(i))
        losses.append(float(loss))
    params = {k: np.asarray(v) for k, v in carry[0].items()}
    aux = {k: np.asarray(v) for k, v in carry[2].items()}
    return losses, np.asarray(outs[0]), params, aux


@pytest.mark.parametrize("axes,mesh_kw", [
    (("dp",), dict(names=("dp",))),
    pytest.param(("dcn", "dp"), dict(names=("dcn", "dp"), shape=(2, 4)),
                 marks=pytest.mark.slow),  # PR 21: tier-1 wall (tests/README.md)
])
def test_fused_trainstep_mesh_matches_single(axes, mesh_kw):
    """Fused-ResNet TrainStep over the mesh == no-mesh step: losses,
    outputs, updated params, and moving stats. The ("dcn","dp") case is
    the multi-host global-mesh layout (spmd_group.py) in one process."""
    sym = _fused_sym()
    mesh = _mesh(8, **mesh_kw)
    ts = TrainStep(sym, functional_optimizer("sgd", learning_rate=0.05),
                   mesh=mesh, data_axes=axes, return_outputs=True)
    batch = 16
    p, _o, a = ts.init_params({"data": (batch, 3, 32, 32),
                               "softmax_label": (batch,)},
                              initializer=mx.initializer.Xavier())
    pn = {k: np.asarray(v) for k, v in p.items()}
    an = {k: np.asarray(v) for k, v in a.items()}
    rng = np.random.RandomState(0)
    batch_np = {
        "data": rng.randn(batch, 3, 32, 32).astype(np.float32),
        "softmax_label": rng.randint(0, 16, (batch,)).astype(np.float32),
    }
    l_mesh, o_mesh, p_mesh, a_mesh = _run_steps(
        ts, pn, an, batch_np, place_sharding=data_sharding(mesh, axes))

    ts1 = TrainStep(sym, functional_optimizer("sgd", learning_rate=0.05),
                    mesh=None, return_outputs=True)
    l_one, o_one, p_one, a_one = _run_steps(ts1, pn, an, batch_np)

    np.testing.assert_allclose(l_mesh, l_one, rtol=2e-5)
    np.testing.assert_allclose(o_mesh, o_one, rtol=2e-4, atol=2e-5)
    for k in p_one:
        np.testing.assert_allclose(p_mesh[k], p_one[k], rtol=2e-4,
                                   atol=2e-6, err_msg=k)
    for k in a_one:
        np.testing.assert_allclose(a_mesh[k], a_one[k], rtol=2e-4,
                                   atol=2e-6, err_msg=k)


@pytest.mark.slow
def test_fused_trainstep_mixed_dp_tp_mesh():
    """Fused Pallas units over dp while fc1 is tensor-sharded over tp —
    the dryrun's mixed-mesh layout with the fused graph: shard_map
    regions (batch axes only) compose with pjit's tp partitioning of
    the dense tail."""
    from jax.sharding import PartitionSpec as P

    sym = _fused_sym()
    mesh = _mesh(8, names=("dp", "tp"), shape=(4, 2))
    rules = [(r".*fc1_weight$", P("tp", None)), (r".*fc1_bias$", P("tp"))]
    ts = TrainStep(sym, functional_optimizer("sgd", learning_rate=0.05),
                   mesh=mesh, data_axes=("dp",), param_rules=rules,
                   return_outputs=True)
    batch = 8
    p, _o, a = ts.init_params({"data": (batch, 3, 32, 32),
                               "softmax_label": (batch,)},
                              initializer=mx.initializer.Xavier())
    pn = {k: np.asarray(v) for k, v in p.items()}
    an = {k: np.asarray(v) for k, v in a.items()}
    rng = np.random.RandomState(1)
    batch_np = {
        "data": rng.randn(batch, 3, 32, 32).astype(np.float32),
        "softmax_label": rng.randint(0, 16, (batch,)).astype(np.float32),
    }
    l_mesh, o_mesh, p_mesh, _a_mesh = _run_steps(
        ts, pn, an, batch_np,
        place_sharding=data_sharding(mesh, ("dp",)))

    ts1 = TrainStep(sym, functional_optimizer("sgd", learning_rate=0.05),
                    mesh=None, return_outputs=True)
    l_one, o_one, p_one, _a_one = _run_steps(ts1, pn, an, batch_np)
    np.testing.assert_allclose(l_mesh, l_one, rtol=2e-5)
    np.testing.assert_allclose(o_mesh, o_one, rtol=2e-4, atol=2e-5)
    for k in ("fc1_weight", "stage1_unit1_conv2_weight",
              "stage2_unit1_bn2_gamma"):
        np.testing.assert_allclose(p_mesh[k], p_one[k], rtol=2e-4,
                                   atol=2e-6, err_msg=k)


@pytest.mark.slow   # PR 21: tier-1 wall (tests/README.md)
def test_parity_catches_dropped_psum(monkeypatch):
    """Planted bug: run the shard_map bwd with axis=None (no psums —
    every shard keeps only its local weight-grad/stat contribution).
    The kernel-level parity test MUST fail, proving it guards the
    cross-shard reductions and not just shapes."""
    orig = fb._unit_bwd

    def buggy(stride, eps, interpret, res, g, axis=None, axis_size=1):
        return orig(stride, eps, interpret, res, g,
                    axis=None, axis_size=axis_size)

    monkeypatch.setattr(fb, "_unit_bwd", buggy)
    with pytest.raises(AssertionError):
        test_bottleneck_spmd_matches_single_device(1, False)


def test_init_params_deterministic():
    """Same seed => identical params: init_params must seed the
    module-owned initializer RNG, not just global numpy (regression —
    cross-process reproducibility of seeded training runs)."""
    sym = _fused_sym()
    ts = TrainStep(sym, functional_optimizer("sgd", learning_rate=0.05),
                   mesh=make_mesh({"dp": 8}))
    shapes = {"data": (16, 3, 32, 32), "softmax_label": (16,)}
    # disturb the module RNG between calls: determinism must not depend
    # on ambient draw position
    from mxnet_tpu import random as rnd_mod

    p1, _, _ = ts.init_params(shapes, initializer=mx.initializer.Xavier())
    rnd_mod.initializer_rng().uniform(size=17)
    p2, _, _ = ts.init_params(shapes, initializer=mx.initializer.Xavier())
    for k in p1:
        np.testing.assert_array_equal(np.asarray(p1[k]), np.asarray(p2[k]),
                                      err_msg=k)
