"""Graph-level IR pass framework (ISSUE 13): rule-based fusion
bit-exactness vs the old fused=True builder emission, the shared
bind-time fold pass, the residual-epilogue rule (a rule, not a matcher
change), int8 post-training-quantized serving, pass determinism, knob
validation, passStats, and the dump_graph CLI."""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, ir, profiler, tune
from mxnet_tpu import symbol as sym
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ir import CalibrationError, Pat, PassError
from mxnet_tpu.models.resnet import resnet
from mxnet_tpu.serving import AOTPredictor, ServingError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(units=[2, 1], num_stages=2, filter_list=[8, 16, 32],
            num_classes=5, image_shape=(3, 64, 64))
TINY_SHAPES = dict(data=(2, 3, 64, 64), softmax_label=(2,))


def _legacy_fused(units, num_stages, filter_list, num_classes,
                  image_shape, bn_mom=0.9):
    """The OLD builder's direct FusedBottleneckUnit emission (the
    fused=True branch this PR replaced) — kept HERE as the
    bit-exactness oracle for the rule-based fusion pass."""
    data = sym.var("data")
    data = sym.identity(data=data, name="id")
    body = sym.Convolution(data=data, num_filter=filter_list[0],
                           kernel=(7, 7), stride=(2, 2), pad=(3, 3),
                           no_bias=True, name="conv0")
    body = sym.BatchNorm(data=body, fix_gamma=False, eps=2e-5,
                         momentum=bn_mom, name="bn0")
    body = sym.Activation(data=body, act_type="relu", name="relu0")
    body = sym.Pooling(data=body, kernel=(3, 3), stride=(2, 2),
                       pad=(1, 1), pool_type="max")
    body = sym.transpose(body, axes=(0, 2, 3, 1), name="to_nhwc")
    for i in range(num_stages):
        s = 1 if i == 0 else 2
        body = sym.FusedBottleneckUnit(
            body, num_filter=filter_list[i + 1], stride=s,
            dim_match=False, eps=2e-5, momentum=bn_mom,
            name="stage%d_unit%d" % (i + 1, 1))
        for j in range(units[i] - 1):
            body = sym.FusedBottleneckUnit(
                body, num_filter=filter_list[i + 1], stride=1,
                dim_match=True, eps=2e-5, momentum=bn_mom,
                name="stage%d_unit%d" % (i + 1, j + 2))
    body = sym.transpose(body, axes=(0, 3, 1, 2), name="to_nchw")
    bn1 = sym.BatchNorm(data=body, fix_gamma=False, eps=2e-5,
                        momentum=bn_mom, name="bn1")
    relu1 = sym.Activation(data=bn1, act_type="relu", name="relu1")
    pool1 = sym.Pooling(data=relu1, global_pool=True, kernel=(7, 7),
                        pool_type="avg", name="pool1")
    fc1 = sym.FullyConnected(data=sym.Flatten(data=pool1),
                             num_hidden=num_classes, name="fc1")
    return sym.SoftmaxOutput(data=fc1, name="softmax")


def _bind_and_run(s, vals, shapes=TINY_SHAPES, backward=True):
    args = set(s.list_arguments())
    ex = s.simple_bind(mx.cpu(), grad_req="write", **shapes)
    auxn = s.list_auxiliary_states()
    _, _, auxsh = s.infer_shape(**shapes)
    ex.copy_params_from(
        {k: v for k, v in vals.items() if k in args},
        dict(zip(auxn, [mx.nd.zeros(v) if "mean" in n else mx.nd.ones(v)
                        for n, v in zip(auxn, auxsh)])))
    out = ex.forward(is_train=True, data=vals["data"],
                     softmax_label=vals["softmax_label"])[0].asnumpy()
    grads = {}
    if backward:
        ex.backward()
        grads = {k: g.asnumpy() for k, g in
                 zip(s.list_arguments(), ex.grad_arrays) if g is not None}
    return out, grads


def _tiny_vals(s, seed=0):
    af, _, _ = s.infer_shape(**TINY_SHAPES)
    args = dict(zip(s.list_arguments(), af))
    rng = np.random.RandomState(seed)
    vals = {k: mx.nd.array(rng.randn(*v).astype(np.float32) * 0.1)
            for k, v in args.items()}
    for k in vals:
        if k.endswith("_gamma"):
            vals[k] = mx.nd.array(np.ones(args[k], np.float32))
    vals["data"] = mx.nd.array(rng.randn(2, 3, 64, 64)
                               .astype(np.float32))
    vals["softmax_label"] = mx.nd.array(
        rng.randint(0, 5, (2,)).astype(np.float32))
    return vals


# ---------------------------------------------------------------------------
# fusion: rules reproduce the old fused=True builder bit-exactly
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_fusion_bit_exact_vs_legacy_emission():
    legacy = _legacy_fused(**TINY)
    fused = resnet(bottle_neck=True, fused=True, **TINY)
    unfused = resnet(bottle_neck=True, fused=False, **TINY)

    # identical parameter surface (names AND shapes) across all three
    al, _, _ = legacy.infer_shape(**TINY_SHAPES)
    af, _, _ = fused.infer_shape(**TINY_SHAPES)
    assert dict(zip(legacy.list_arguments(), al)) == \
        dict(zip(fused.list_arguments(), af))
    assert sorted(legacy.list_auxiliary_states()) == \
        sorted(fused.list_auxiliary_states())
    assert sorted(unfused.list_arguments()) == \
        sorted(fused.list_arguments())

    vals = _tiny_vals(fused)
    out_l, g_l = _bind_and_run(legacy, vals)
    out_f, g_f = _bind_and_run(fused, vals)
    out_u, _ = _bind_and_run(unfused, vals, backward=False)
    # the pass-built graph IS the legacy graph: bit-exact fwd AND grads
    np.testing.assert_array_equal(out_l, out_f)
    for k in g_l:
        np.testing.assert_array_equal(g_l[k], g_f[k])
    # and numerically the same network as the unfused build
    np.testing.assert_allclose(out_f, out_u, atol=2e-4)


def _graph_signature(s):
    """Canonical structural signature: per topo node (op|var name,
    sorted attrs, input refs as topo indices) — names of op nodes
    excluded (the pass auto-names transposes)."""
    nodes = s._topo()
    index = {id(n): i for i, n in enumerate(nodes)}
    sig = []
    for n in nodes:
        if n.is_variable():
            sig.append(("var", n.name))
            continue
        attrs = tuple(sorted((k, repr(v)) for k, v in n.attrs.items()))
        ins = tuple((index[id(i)], idx) for i, idx in n.inputs)
        sig.append((n.op.name, attrs, ins))
    return sig


@pytest.mark.slow   # PR 21: tier-1 wall (tests/README.md)
def test_fusion_schedule_keys_identical():
    """Acceptance: build_resnet(fused=True) and the pass-fused unfused
    graph consult IDENTICAL schedule-table keys. Checked two ways:
    trace-time consult recording on the tiny net (executed), and
    structural graph equality vs the legacy emission at the ResNet-50
    bench shape (not executed — the consult key is a pure function of
    the graph)."""
    # (1) trace-time: record every schedule_for consult while running
    consults = []
    real = tune.schedule_for

    def recorder(kernel, shape, dtype, backend=None):
        consults.append((kernel, tuple(shape), str(dtype)))
        return real(kernel, shape, dtype, backend)

    legacy = _legacy_fused(**TINY)
    fused = resnet(bottle_neck=True, fused=True, **TINY)
    vals = _tiny_vals(fused)
    tune.schedule_for, keys = recorder, {}
    try:
        for name, s in (("legacy", legacy), ("pass", fused)):
            consults.clear()
            _bind_and_run(s, vals, backward=False)
            keys[name] = sorted(set(consults))
    finally:
        tune.schedule_for = real
    assert keys["legacy"] == keys["pass"]
    assert keys["pass"], "fused graph never consulted the table"

    # (2) bench shape: structurally identical graphs => identical keys
    spec = dict(units=[3, 4, 6, 3], num_stages=4,
                filter_list=[64, 256, 512, 1024, 2048],
                num_classes=1000, image_shape=(3, 224, 224))
    big_legacy = _legacy_fused(**spec)
    big_fused = resnet(bottle_neck=True, fused=True, **spec)
    assert _graph_signature(big_legacy) == _graph_signature(big_fused)


def test_fuse_kill_switch_and_knob_validation(monkeypatch):
    monkeypatch.setenv("MXNET_IR_FUSE", "0")
    s = resnet(bottle_neck=True, fused=True, **TINY)
    assert not any(not n.is_variable()
                   and n.op.name == "FusedBottleneckUnit"
                   for n in s._topo())
    monkeypatch.setenv("MXNET_IR_FUSE", "maybe")
    with pytest.raises(MXNetError, match="MXNET_IR_FUSE"):
        resnet(bottle_neck=True, fused=True, **TINY)
    monkeypatch.setenv("MXNET_IR_PASSES", "bogus")
    with pytest.raises(MXNetError, match="MXNET_IR_PASSES"):
        ir.apply_passes(resnet(bottle_neck=True, fused=False, **TINY))


def test_pass_order_determinism():
    base = resnet(bottle_neck=True, fused=False, **TINY)
    m1 = ir.PassManager(("fusion",))
    s1, prov1 = m1.apply(base)
    s2, prov2 = ir.PassManager(("fusion",)).apply(base)
    assert s1.tojson() == s2.tojson()
    assert prov1 == prov2
    assert prov1[0]["applied"].count("bottleneck_fuse") == 3


# ---------------------------------------------------------------------------
# matcher unit behavior
# ---------------------------------------------------------------------------
def test_matcher_shared_pat_and_boundary():
    x = sym.var("x")
    y = x + x          # both add inputs are THE SAME entry
    z = x + sym.var("w")
    shared = Pat(name="a")
    pat_same = Pat("broadcast_add", inputs=[shared, shared])
    assert ir.match(pat_same, y._entries[0]) is not None
    assert ir.match(pat_same, z._entries[0]) is None
    # wildcards are boundaries: cannot carry constraints
    with pytest.raises(MXNetError):
        Pat(attrs={"kernel": (1, 1)})


def test_pass_error_names_rule_and_node():
    class BadRule(ir.Rule):
        name = "bad_rule"
        pattern = Pat("Activation", inputs=[Pat(name="x")])

        def rewrite(self, m):
            from mxnet_tpu.symbol.symbol import Symbol, _Node
            from mxnet_tpu.ops import registry

            node = _Node(registry.get("Convolution"), {}, [], "broken")
            return Symbol([(node, 0)])

    act = sym.Activation(sym.var("d"), act_type="relu", name="theact")
    with pytest.raises(PassError) as err:
        ir.RulePass("p", [BadRule()]).apply(act)
    assert "bad_rule" in str(err.value) and "theact" in str(err.value)


# ---------------------------------------------------------------------------
# residual-add-into-conv-epilogue: a rule, zero matcher edits
# ---------------------------------------------------------------------------
def test_residual_rule_bit_exact():
    base = resnet(bottle_neck=False, fused=False, **TINY)
    rewritten = ir.apply_passes(base, passes=("residual",))
    ops = [n.op.name for n in rewritten._topo() if not n.is_variable()]
    assert ops.count("_ConvResidualAdd") == 3
    assert ops.count("broadcast_add") == 0
    assert sorted(base.list_arguments()) == \
        sorted(rewritten.list_arguments())
    vals = _tiny_vals(base)
    out_b, _ = _bind_and_run(base, vals, backward=False)
    out_r, _ = _bind_and_run(rewritten, vals, backward=False)
    np.testing.assert_array_equal(out_b, out_r)


def test_rule_kernels_feed_the_autotuner():
    """Rules name kernels; tune/ exposes them as sweepable — and a NEW
    rule's kernel lands in the sweep set with zero tune/ edits."""
    rk = tune.rule_kernels()
    assert rk["bottleneck_fuse"] == ("fused_fwd", "fused_wgrad",
                                     "fused_dgrad")
    assert rk["residual_conv_epilogue"] == ("fused_fwd",)
    assert set(tune.SWEEPABLE_KERNELS) <= set(tune.sweepable_kernels())

    class NewRule(ir.Rule):
        name = "test_newrule"
        kernels = ("my_new_kernel",)
        pattern = Pat("Activation", inputs=[Pat()])

        def rewrite(self, m):  # pragma: no cover - never applied
            raise AssertionError

    ir.register_rule(NewRule())
    try:
        assert "my_new_kernel" in tune.sweepable_kernels()
        assert tune.rule_kernels()["test_newrule"] == ("my_new_kernel",)
    finally:
        from mxnet_tpu.ir import rules as _rules

        del _rules._RULES["test_newrule"]


# ---------------------------------------------------------------------------
# shared bind-time fold pass
# ---------------------------------------------------------------------------
def test_fold_plan_shared_with_predictor():
    d = sym.var("data")
    w1, w2, b = (sym.var(n, shape=(4,)) for n in ("w1", "w2", "b"))
    folded_part = w1 + w2             # pure function of the weights
    net = d * folded_part + b
    plan = ir.FoldPlan(net, {"data"})
    assert plan.folded_nodes == 1     # the (w1 + w2) node
    assert ("node", plan.fold_order[0], 0) in plan.const_specs
    assert ("var", "b") in plan.const_specs

    rng = np.random.RandomState(0)
    params = {k: rng.randn(4).astype(np.float32)
              for k in ("w1", "w2", "b")}
    profiler.pass_reset()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        pred = AOTPredictor(net, params,
                            data_shapes={"data": (1, 4)}, ladder=(4,))
    assert pred.bind_stats["folded_nodes"] == 1
    x = rng.randn(4, 4).astype(np.float32)
    expect = x * (params["w1"] + params["w2"]) + params["b"]
    np.testing.assert_allclose(pred.predict(x)[0], expect, rtol=1e-6)
    stats = profiler.pass_stats()
    assert stats["passes"]["fold"]["folded_nodes"] >= 1


# ---------------------------------------------------------------------------
# int8 post-training quantization
# ---------------------------------------------------------------------------
def _trained_mlp():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    from bench_serve import _train_model, build_model

    net, _ = build_model(64, 128, 3, 16)
    args_np, sample = _train_model(net, 64, 16, epochs=5, n=2048,
                                   batch=128)
    return net, args_np, sample


@pytest.fixture(scope="module")
def trained_mlp():
    return _trained_mlp()


def test_int8_agreement_and_binding(trained_mlp):
    net, args_np, sample = trained_mlp
    calib = [{"data": sample(64, 500 + i)[0]} for i in range(4)]
    corpus, labels = sample(1024, 900)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        pb = AOTPredictor(net, args_np, data_shapes={"data": (1, 64)},
                          ladder=(1024,), dtype="bfloat16")
        pq = AOTPredictor(net, args_np, data_shapes={"data": (1, 64)},
                          ladder=(1024,), quant="int8", calib_data=calib)
    # binding surface unchanged: same args, ladder/cache machinery
    assert pq.bind_stats["quant"] == "int8"
    assert pq.bind_stats["quantized_ops"] == 4  # 3 hidden + head
    top_b = np.argmax(pb.predict(corpus)[0], 1)
    top_q = np.argmax(pq.predict(corpus)[0], 1)
    agreement = float((top_q == top_b).mean())
    assert agreement >= 0.99, agreement
    # weights are quantized ahead of time BY THE FOLD PASS: the int8
    # weight tables are in the folded consts, so a swap requantizes
    swapped = {k: v + 0.01 * np.abs(v).max()
               * np.random.RandomState(3).randn(*v.shape)
               .astype(np.float32) for k, v in args_np.items()}
    pq.swap_params(swapped)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        pb2 = AOTPredictor(net, swapped, data_shapes={"data": (1, 64)},
                           ladder=(1024,), dtype="bfloat16")
    top_q2 = np.argmax(pq.predict(corpus)[0], 1)
    top_b2 = np.argmax(pb2.predict(corpus)[0], 1)
    assert float((top_q2 == top_b2).mean()) >= 0.99


def test_int8_requires_calibration(trained_mlp):
    net, args_np, _sample = trained_mlp
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(CalibrationError):
            AOTPredictor(net, args_np, data_shapes={"data": (1, 64)},
                         quant="int8")
        with pytest.raises(CalibrationError):
            AOTPredictor(net, args_np, data_shapes={"data": (1, 64)},
                         quant="int8", calib_data=[])
    with pytest.raises(ServingError, match="quant"):
        AOTPredictor(net, args_np, data_shapes={"data": (1, 64)},
                     quant="float7")


def test_quant_knob_validation(trained_mlp, monkeypatch):
    net, args_np, sample = trained_mlp
    monkeypatch.setenv("MXNET_SERVE_QUANT", "int7")
    with pytest.raises(MXNetError, match="MXNET_SERVE_QUANT"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            AOTPredictor(net, args_np, data_shapes={"data": (1, 64)})
    monkeypatch.setenv("MXNET_SERVE_QUANT", "none")
    monkeypatch.setenv("MXNET_QUANT_CALIB_BATCHES", "-3")
    calib = [{"data": sample(16, 501)[0]}]
    with pytest.raises(MXNetError, match="MXNET_QUANT_CALIB_BATCHES"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            AOTPredictor(net, args_np, data_shapes={"data": (1, 64)},
                         quant="int8", calib_data=calib)


def test_int8_conv_path():
    """The conv flavor: a small conv net quantizes, binds, and tracks
    the float forward closely (logits-level; per-channel weight
    scales)."""
    d = sym.var("data")
    c1 = sym.Convolution(data=d, num_filter=8, kernel=(3, 3),
                         pad=(1, 1), name="c1")
    r1 = sym.Activation(data=c1, act_type="relu")
    c2 = sym.Convolution(data=r1, num_filter=8, kernel=(3, 3),
                         pad=(1, 1), name="c2")
    net = sym.FullyConnected(data=sym.Flatten(data=c2), num_hidden=4,
                             name="out")
    rng = np.random.RandomState(0)
    shapes = {"data": (2, 3, 8, 8)}
    arg_shapes, _, _ = net.infer_shape(**shapes)
    params = {n: (rng.randn(*s) * 0.2).astype(np.float32)
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n != "data"}
    calib = [{"data": rng.randn(2, 3, 8, 8).astype(np.float32)}
             for _ in range(3)]
    qsym, report = ir.quantize_for_serving(net, params, calib, ["data"])
    ops = [n.op.name for n in qsym._topo() if not n.is_variable()]
    assert ops.count("_int8_convolution") == 2
    assert ops.count("_int8_fully_connected") == 1
    assert report["quantized_ops"] == 3
    pf = AOTPredictor(net, params, data_shapes={"data": (1, 3, 8, 8)},
                      ladder=(4,))
    pq = AOTPredictor(qsym, params, data_shapes={"data": (1, 3, 8, 8)},
                      ladder=(4,))
    x = rng.randn(4, 3, 8, 8).astype(np.float32)
    of, oq = pf.predict(x)[0], pq.predict(x)[0]
    scale = np.abs(of).max() + 1e-6
    assert np.abs(of - oq).max() / scale < 0.05


def test_shared_cache_keys_carry_quant_fingerprint(trained_mlp):
    """Two predictors under ONE model name on a shared cache — one
    int8, one float — must not resolve to each other's executables
    (the scales are baked into the traced programs)."""
    from mxnet_tpu.serving import ExecutableCache

    net, args_np, sample = trained_mlp
    calib = [{"data": sample(32, 777)[0]}]
    cache = ExecutableCache(capacity=8)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        pf = AOTPredictor(net, args_np, data_shapes={"data": (1, 64)},
                          ladder=(8,), cache=cache, model_name="m")
        pq = AOTPredictor(net, args_np, data_shapes={"data": (1, 64)},
                          ladder=(8,), cache=cache, model_name="m",
                          quant="int8", calib_data=calib)
        calib2 = [{"data": 3.0 * sample(32, 778)[0]}]
        pq2 = AOTPredictor(net, args_np, data_shapes={"data": (1, 64)},
                           ladder=(8,), cache=cache, model_name="m",
                           quant="int8", calib_data=calib2)
    x = sample(8, 779)[0]
    of, oq, oq2 = (p.predict(x)[0] for p in (pf, pq, pq2))
    assert cache.compiles == 3  # three distinct keys, zero cross-serves
    assert not np.array_equal(of, oq)
    assert not np.array_equal(oq, oq2)  # different calibration scales


def test_calib_batches_reports_consumed_count(trained_mlp, monkeypatch):
    """The report counts batches actually evaluated, not provided."""
    net, args_np, sample = trained_mlp
    monkeypatch.setenv("MXNET_QUANT_CALIB_BATCHES", "2")
    calib = [{"data": sample(16, 600 + i)[0]} for i in range(5)]
    params = {k: v for k, v in args_np.items()}
    _qsym, report = ir.quantize_for_serving(net, params, calib, ["data"])
    assert report["calib_batches"] == 2


def test_quantize_skips_computed_bias():
    """An FC whose bias is a computed node is neither calibrated (no
    gauge, no fingerprint entry) nor rewritten — the invariant is one
    calibration gauge per QUANTIZED boundary."""
    d = sym.var("data")
    b0 = sym.var("b0", shape=(2,))
    fc = sym.FullyConnected(data=d, num_hidden=2, bias=b0 * 2.0,
                            name="fcb")
    rng = np.random.RandomState(0)
    shapes = {"data": (2, 4)}
    arg_shapes, _, _ = fc.infer_shape(**shapes)
    params = {n: (rng.randn(*s) * 0.2).astype(np.float32)
              for n, s in zip(fc.list_arguments(), arg_shapes)
              if n != "data"}
    calib = [{"data": rng.randn(2, 4).astype(np.float32)}]
    qsym, report = ir.quantize_for_serving(fc, params, calib, ["data"])
    assert report.get("quantized_ops", 0) == 0
    assert not report.get("calibration")
    ops = [n.op.name for n in qsym._topo() if not n.is_variable()]
    assert "_int8_fully_connected" not in ops


def test_quantize_skips_non_2d_convs():
    """1-D convs stay float: _int8_convolution is NCHW/OIHW only."""
    d = sym.var("data")
    c = sym.Convolution(data=d, num_filter=4, kernel=(3,), pad=(1,),
                        name="c1d")
    net = sym.FullyConnected(data=sym.Flatten(data=c), num_hidden=2,
                             name="out")
    rng = np.random.RandomState(0)
    shapes = {"data": (2, 3, 8)}
    arg_shapes, _, _ = net.infer_shape(**shapes)
    params = {n: (rng.randn(*s) * 0.2).astype(np.float32)
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n != "data"}
    calib = [{"data": rng.randn(2, 3, 8).astype(np.float32)}]
    qsym, report = ir.quantize_for_serving(net, params, calib, ["data"])
    ops = [n.op.name for n in qsym._topo() if not n.is_variable()]
    assert ops.count("Convolution") == 1       # untouched
    assert ops.count("_int8_convolution") == 0
    assert ops.count("_int8_fully_connected") == 1
    assert report["quantized_ops"] == 1


# ---------------------------------------------------------------------------
# observability + CLI
# ---------------------------------------------------------------------------
def test_pass_stats_ride_dump_profile(tmp_path):
    profiler.pass_reset()
    ir.apply_passes(resnet(bottle_neck=True, fused=False, **TINY),
                    passes=("fusion",))
    stats = profiler.pass_stats()
    fusion = stats["passes"]["fusion"]
    assert fusion["rules"]["bottleneck_fuse"] == 3
    assert fusion["rules"]["transpose_cancel"] == 2
    assert fusion["nodes_rewritten"] > 0
    with pytest.raises(ValueError, match="unknown counter"):
        profiler.pass_record("fusion", typo_counter=1)
    out = tmp_path / "profile.json"
    profiler.profiler_set_config(filename=str(out))
    profiler.dump_profile()
    payload = json.loads(out.read_text())
    assert "passStats" in payload
    assert payload["passStats"]["passes"]["fusion"]["hits"] == 5
    profiler.pass_reset()
    assert profiler.pass_stats() == {}


def test_dump_graph_cli():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "dump_graph.py"),
         "--model", "resnet", "--tiny", "--passes", "fusion",
         "--shapes", "data:2,3,64,64;softmax_label:2", "--json"],
        capture_output=True, text=True, timeout=240,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-500:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    fusion = rec["passes"][0]
    assert fusion["rewrites"] == 5
    assert fusion["op_delta"]["FusedBottleneckUnit"] == 3
    assert rec["final_ops"]["transpose"] == 2
