"""MXU-blocked fused kernels (round-6 rewrite of kernels/fused_block.py).

Two contracts, both CPU-checkable:

1. **Parity** — the (channel-block, batch-block, row-tile) grid with
   batch folded into the matmul rows computes the same network as the
   unfused graph, in interpret mode, at the three ResNet bottleneck
   block flavors (stride-1 dim-match, stride-1 projection, stride-2
   projection), forward AND backward — including grids forced to
   multiple batch-blocks and channel-blocks (the paths the tiny shapes
   in test_fused_resnet.py never reach, because their whole batch fits
   one block).

2. **MXU-work floor** — at the real ResNet-50 shapes the bench runs
   (batch 256), every kernel's plan gives each MXU call
   >= (256x256)x256 multiply-accumulates (``mxu_plan``): the quantified
   fix for the round-5 on-chip result where 196-row matmuls against
   64-wide channels left the fused path 2.5x behind XLA.

tools/bench_kernel.py's loop-amortized harness gets a plumbing smoke
here too, so the benchmark that decides fused-vs-unfused labeling
cannot rot unnoticed.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from mxnet_tpu.kernels import fused_block as fb

EPS = 2e-5
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# reference graph (same math as the unfused symbolic builder)
# ---------------------------------------------------------------------------
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


def _unit_args(stride, dim_match, seed, n, h, w, ci, c, co=None):
    co = co if co is not None else (ci if dim_match else 2 * ci)
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))  # noqa
    return (f(n, h, w, ci), f(1, 1, ci, c), f(3, 3, c, c), f(1, 1, c, co),
            None if dim_match else f(1, 1, ci, co),
            f(ci) + 1.0, f(ci) * 0.1, f(c) + 1.0, f(c) * 0.1,
            f(c) + 1.0, f(c) * 0.1)


def _assert_unit_parity(args, stride, atol=3e-4, gtol=1e-3):
    out_f, stats = fb.bottleneck_train(*args, stride, EPS, True)
    out_r = _ref_unit(*args, stride)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                               atol=atol)
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
        assert float(jnp.max(jnp.abs(a - b))) / scale < gtol


# ---------------------------------------------------------------------------
# 1. parity at the three block flavors, multi-block grids forced
# ---------------------------------------------------------------------------
@pytest.mark.slow   # PR 21: tier-1 wall (tests/README.md)
@pytest.mark.parametrize("stride,dim_match", [(1, True), (1, False),
                                              (2, False)])
def test_parity_multi_batch_block_grid(stride, dim_match, monkeypatch):
    """Shrink the VMEM budget so the batch fold is capped below N and
    the grid runs multiple batch-blocks (nbb > 1) — the production
    geometry at batch 256, which full-batch folds never exercise."""
    monkeypatch.setattr(fb, "_VMEM_BLOCK_ELEMS", 1024)
    args = _unit_args(stride, dim_match, seed=1, n=4, h=8, w=8, ci=8, c=8)
    plan = fb.mxu_plan("fwd", args[0].shape, np.asarray(args[1]).shape)
    assert plan["grid"][1] > 1, "budget cap failed to split the batch"
    _assert_unit_parity(args, stride)


@pytest.mark.slow   # PR 21: tier-1 wall (tests/README.md)
@pytest.mark.parametrize("stride,dim_match", [(1, True), (2, False)])
def test_parity_channel_blocked_grid(stride, dim_match):
    """co=512 output convs split into two 256-lane channel blocks
    (cb > 1) while spatial dims stay tiny — covers the blocked weight /
    output / stats index maps."""
    args = _unit_args(stride, dim_match, seed=2, n=2, h=4, w=4,
                      ci=512, c=8, co=512)
    plan = fb.mxu_plan("fwd", (2, 4, 4, 8), (1, 1, 8, 512))
    assert plan["grid"][0] == 2, plan
    _assert_unit_parity(args, stride, atol=2e-3, gtol=2e-3)


def test_conv_kernels_channel_blocked_parity():
    """Kernel-level fwd/wgrad/dgrad parity (vs jax.vjp of the reference
    conv) when Co and Ci exceed the 256-lane block."""
    rng = np.random.RandomState(3)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))  # noqa
    n, h, w, ci, co = 2, 4, 4, 512, 512
    x, wt = f(n, h, w, ci), f(3, 3, ci, co)
    g = f(n, h, w, co)

    y, stats = fb.conv_fwd(x, wt, stride=1, emit_stats=True, interpret=True)
    ref, vjp = jax.vjp(lambda a, b: _ref_conv(a, b, 1), x, wt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=2e-3)
    np.testing.assert_allclose(
        np.asarray(stats[0]), np.asarray(jnp.sum(ref, (0, 1, 2))),
        rtol=1e-5, atol=1e-3)

    dx_ref, dw_ref = vjp(g)
    dw = fb.conv_wgrad(x, g, wt.shape, stride=1, interpret=True)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               rtol=1e-4, atol=2e-3)
    dx, _ = fb.conv_dgrad(g, wt, x.shape, stride=1, interpret=True)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=1e-4, atol=2e-3)


@pytest.mark.slow   # PR 21: tier-1 wall (tests/README.md)
def test_row_tile_knob():
    """set_row_tile (and the env knob behind it) changes the planned
    row tile and keeps parity."""
    args = _unit_args(1, True, seed=4, n=2, h=8, w=8, ci=8, c=8)
    try:
        fb.set_row_tile(2)
        assert fb.mxu_plan("fwd", (2, 8, 8, 8), (1, 1, 8, 8))["th"] == 2
        _assert_unit_parity(args, 1)
    finally:
        fb.set_row_tile(None)
    assert fb.mxu_plan("fwd", (2, 8, 8, 8), (1, 1, 8, 8))["th"] == 8


# ---------------------------------------------------------------------------
# 2. the MXU-work floor at the real bench shapes
# ---------------------------------------------------------------------------
def _resnet50_convs(batch=256):
    """Every distinct (x_shape, w_shape, stride) conv the fused ResNet-50
    residual stack runs at the bench batch."""
    convs = []
    spatial = {1: 56, 2: 28, 3: 14, 4: 7}
    chans = {1: (256, 64), 2: (512, 128), 3: (1024, 256), 4: (2048, 512)}
    for stage in (1, 2, 3, 4):
        hw = spatial[stage] * (2 if stage > 1 else 1)   # pre-downsample
        cin_prev = 64 if stage == 1 else chans[stage - 1][0]
        cin, csq = chans[stage]
        s = 1 if stage == 1 else 2
        # first (projection) unit
        convs.append(((batch, hw, hw, cin_prev), (1, 1, cin_prev, csq), 1))
        convs.append(((batch, hw, hw, csq), (3, 3, csq, csq), s))
        convs.append(((batch, hw // s, hw // s, csq), (1, 1, csq, cin), 1))
        convs.append(((batch, hw, hw, cin_prev), (1, 1, cin_prev, cin), s))
        # dim-match units
        convs.append(((batch, hw // s, hw // s, cin), (1, 1, cin, csq), 1))
        convs.append(((batch, hw // s, hw // s, csq), (3, 3, csq, csq), 1))
    return convs


def test_mxu_work_floor_at_bench_shapes():
    """The tentpole contract: at batch 256, EVERY conv in the fused
    ResNet-50 stack — forward, wgrad, and dgrad — plans matmul tiles
    meeting the (256x256)x256 MXU-work floor."""
    for kind in ("fwd", "wgrad", "dgrad"):
        for x_shape, w_shape, stride in _resnet50_convs():
            p = fb.mxu_plan(kind, x_shape, w_shape, stride=stride)
            assert p["work"] >= p["floor"], (kind, x_shape, w_shape,
                                             stride, p)
            # the plan must be realizable: blocks divide their axes
            cdim, nbb, ht = p["grid"]
            assert nbb * p["nb"] == x_shape[0]
            n_axis = w_shape[-1] if kind in ("fwd", "wgrad") else w_shape[2]
            assert cdim * p["bco"] == n_axis


def test_mxu_floor_not_met_on_tiny_shapes_is_reported():
    """mxu_plan reports honestly below the floor (tiny CPU-test shapes
    cannot meet it); kernels still run there — the floor is a bench
    contract, not a runtime gate."""
    p = fb.mxu_plan("fwd", (2, 8, 8, 8), (3, 3, 8, 8))
    assert p["work"] < p["floor"]


# ---------------------------------------------------------------------------
# 3. the loop-amortized benchmark harness is runnable (plumbing smoke)
# ---------------------------------------------------------------------------
@pytest.mark.slow   # PR 21: tier-1 wall (tests/README.md)
def test_bench_kernel_harness_smoke():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "bench_kernel.py"),
         "--cpu", "--batch", "1", "--hw", "4", "--ci", "8", "--co", "8",
         "--unit-cin", "8", "--iters", "3", "--repeats", "2"],
        capture_output=True, text=True, timeout=420, env=env, cwd=ROOT)
    assert proc.returncode in (0, 4), proc.stdout + proc.stderr
    last = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")][-1]
    rec = json.loads(last)
    assert "conv3x3_fwd_pallas" in rec["bench_kernel"]
    assert "unit_fwdbwd_xla" in rec["bench_kernel"]
    for r in rec["bench_kernel"].values():
        # 3-iteration micro-runs can round to 0.0 ms of process-CPU;
        # the smoke only proves the harness plumbing end-to-end
        assert r["ms_per_iter"] >= 0
        assert r["iters"] >= 3 and len(r["runs_ms"]) == 2
    # ISSUE 10 satellite: pallas conv records carry the mxu_plan
    # summary + the schedule-table key, so bench records and table
    # entries are join-able
    for name in ("conv3x3_fwd_pallas", "conv1x1_fwd_pallas"):
        r = rec["bench_kernel"][name]
        plan = r["mxu_plan"]
        assert plan["work"] == plan["m"] * plan["k"] * plan["n"]
        assert len(plan["grid"]) == 3
        assert r["schedule_key"].startswith("fused_fwd|")
        assert r["schedule_key"].endswith("|bfloat16|cpu")
    assert "mxu_plan" not in rec["bench_kernel"]["conv3x3_fwd_xla"]
    assert rec["tuned"] is False
