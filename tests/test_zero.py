"""Weight-update sharding (ZeRO, ISSUE 7) on the fused SPMD tier.

Reference bar: arXiv:2004.13336 ("Automatic Cross-Replica Sharding of
Weight Update in Data-Parallel Training") — reduce-scatter grads,
update a 1/N optimizer-state shard, all-gather weights, numerically
identical to the replicated update. Runs on the virtual 8-device CPU
mesh (SURVEY §4); wall time in tests/README.md.
"""
import json
import pickle

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import get_symbol
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.spmd import TrainStep, functional_optimizer


def _uneven_symbol():
    """fc1_weight (13, 33) = 429 elements, 429 % 8 != 0 — the padded
    uneven-shard case; fc1_bias (13,) stays below every min-size."""
    return mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(
            mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=13,
                                  name="fc1"),
            num_hidden=10, name="fc2"),
        name="softmax")


def _batch(n=16, dim=33, classes=10, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "data": rng.randn(n, dim).astype(np.float32),
        "softmax_label": rng.randint(0, classes, (n,)).astype(np.float32),
    }


def _run_steps(opt_kwargs, zero, steps=5, compute_dtype=None, seed=3,
               zero_wire=None):
    import jax

    ts = TrainStep(_uneven_symbol(), functional_optimizer(**opt_kwargs),
                   mesh=make_mesh({"dp": 8}), zero=zero,
                   zero_min_size=16, compute_dtype=compute_dtype,
                   zero_wire=zero_wire)
    params, st, aux = ts.init_params(
        {"data": (16, 33), "softmax_label": (16,)}, seed=seed)
    carry = ts.place(params, st, aux)
    batch = _batch()
    key = jax.random.PRNGKey(0)
    losses = []
    for _ in range(steps):
        carry, loss = ts(carry, batch, key)
        losses.append(float(loss))
    return ts, carry, losses


@pytest.mark.parametrize("opt_kwargs", [
    dict(name="sgd", learning_rate=0.1),
    dict(name="sgd", learning_rate=0.1, momentum=0.9, wd=1e-4),
    dict(name="adam", learning_rate=1e-3, wd=1e-4),
], ids=["sgd", "sgd-mom-wd", "adam"])
def test_zero_matches_replicated(opt_kwargs):
    """The sharded update is the SAME math as the replicated one —
    params bit-close after K steps, loss trajectory identical — across
    optimizers, weight decay, and an uneven param_size % 8 != 0 shape
    (the padding lanes must stay inert)."""
    import jax

    _, c_rep, l_rep = _run_steps(opt_kwargs, zero=False)
    ts, c_zero, l_zero = _run_steps(opt_kwargs, zero=True)
    np.testing.assert_allclose(l_rep, l_zero, rtol=1e-5)
    p_rep, p_zero = jax.device_get((c_rep[0], c_zero[0]))
    for k in p_rep:
        np.testing.assert_allclose(p_rep[k], p_zero[k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)
    # the plan sharded the big weights and left the tiny biases alone
    plan = ts.zero_plan(c_zero[0])
    assert "fc1_weight" in plan and "fc1_bias" not in plan
    # momentum/adam state for planned params lives as its 1/N shard
    if opt_kwargs["name"] != "sgd" or opt_kwargs.get("momentum"):
        from jax.sharding import PartitionSpec as P

        leaf = jax.tree_util.tree_leaves(c_zero[1]["fc1_weight"])[0]
        assert leaf.sharding.spec == P(("dp",), None)
        assert leaf.shape == (8, plan["fc1_weight"][3])


def test_zero_matches_replicated_bf16():
    """bf16 compute / fp32 master weights: same parity bar (grads are
    bf16, the update runs fp32 on both paths)."""
    import jax

    kw = dict(name="sgd", learning_rate=0.1, momentum=0.9)
    _, c_rep, l_rep = _run_steps(kw, zero=False, compute_dtype="bfloat16")
    _, c_zero, l_zero = _run_steps(kw, zero=True, compute_dtype="bfloat16")
    np.testing.assert_allclose(l_rep, l_zero, rtol=1e-4)
    p_rep, p_zero = jax.device_get((c_rep[0], c_zero[0]))
    # atol: bfloat16 gradients summed in another order move an update of
    # ~1e-2 by up to its 2**-8 = 4e-5 (observed 1.7e-5); a broken shard 1e-2
    for k in p_rep:
        np.testing.assert_allclose(p_rep[k], p_zero[k],
                                   rtol=1e-4, atol=5e-5, err_msg=k)


def test_zero_opt_state_bytes_scale_1_over_n(tmp_path):
    """The acceptance memory bar: measured per-device optimizer-state
    bytes under zero=True are <= 1/4 of the replicated baseline on the
    8-device mesh (expected ~1/8 for the sharded keys), read from the
    new profiler memory_stats surface; the gauge rides dump_profile."""
    kw = dict(name="sgd", learning_rate=0.1, momentum=0.9)
    ts_r, c_rep, _ = _run_steps(kw, zero=False, steps=1)
    repl = ts_r.memory_stats(c_rep)
    ts_z, c_zero, _ = _run_steps(kw, zero=True, steps=1)
    zero = ts_z.memory_stats(c_zero)
    assert zero["zero"] and zero["num_shards"] == 8
    assert zero["opt_bytes_per_dev"] <= repl["opt_bytes_per_dev"] / 4
    # params stay replicated (ZeRO stage 1: state only)
    assert zero["param_bytes_per_dev"] == repl["param_bytes_per_dev"]
    # the gauge holds the LAST placed carry and rides dump_profile
    ts_z.record_memory_stats(c_zero)
    assert profiler.memory_stats()["opt_bytes_per_dev"] == \
        zero["opt_bytes_per_dev"]
    out = tmp_path / "profile.json"
    profiler.profiler_set_config(filename=str(out))
    try:
        profiler.dump_profile()
    finally:
        profiler.profiler_set_config(filename="profile.json")
    assert json.loads(out.read_text())["memoryStats"]["zero"] is True


@pytest.mark.slow
def test_zero_wire_2bit_quantizes_with_sharded_residual():
    """zero_wire='2bit': the reduce-scattered gradient shard round-trips
    the PR 4 packed wire codes with an error-feedback residual that is
    itself 1/N-sharded; training still converges (error feedback), and
    the quantized path genuinely differs from raw per step."""
    import jax
    from jax.sharding import PartitionSpec as P

    kw = dict(name="sgd", learning_rate=0.05, momentum=0.9)
    _, c_raw, l_raw = _run_steps(kw, zero=True, steps=25)
    ts, c_q, l_q = _run_steps(kw, zero=True, steps=25, zero_wire="2bit")
    res = c_q[1][TrainStep._ZERO_RES]
    assert set(res) == set(ts.zero_plan(c_q[0]))
    for r in res.values():
        assert r.sharding.spec == P(("dp",), None)
    assert not np.allclose(l_raw[1:], l_q[1:])  # it really quantized
    assert l_q[-1] < l_q[0]  # error feedback keeps it training
    assert np.isfinite(l_q).all()


def _fit_module(monkeypatch, zero_env, steps=3, seed=0):
    monkeypatch.setenv("MXNET_TPU_ZERO", zero_env)
    sym = get_symbol("mlp", num_classes=16)
    mod = mx.mod.Module(sym, context=[mx.cpu(i) for i in range(8)])
    mod.bind(data_shapes=[("data", (16, 32))],
             label_shapes=[("softmax_label", (16,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore="tpu", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    assert mod._fused is not None
    rng = np.random.RandomState(seed)
    for _ in range(steps):
        batch = mx.io.DataBatch(
            data=[mx.nd.array(rng.randn(16, 32).astype(np.float32))],
            label=[mx.nd.array(rng.randint(0, 16, (16,))
                               .astype(np.float32))])
        mod.forward_backward(batch)
        mod.update()
    return mod


def test_module_zero_knob_and_sharded_checkpoint_roundtrip(
        monkeypatch, tmp_path):
    """The exposure + checkpoint acceptance: MXNET_TPU_ZERO=1 reaches
    Module.fit users without touching jax, and optimizer states saved
    under zero=True restore bit-exactly under zero=False (and back) —
    the blob stores the mesh-size-independent logical layout."""
    mod_z = _fit_module(monkeypatch, "1")
    assert mod_z._fused._ts.zero is True
    st_z = str(tmp_path / "zero.states")
    mod_z.save_optimizer_states(st_z)
    blob_z = pickle.loads(open(st_z, "rb").read())
    assert blob_z["zero"] is True
    # logical layout: every state array is param-shaped, not (8, chunk)
    params = {k: v for k, v in mod_z._fused._carry[0].items()}
    for k, v in blob_z["opt_state"].items():
        assert tuple(np.asarray(v).shape) == tuple(params[k].shape), k

    # restore under zero=False: bit-exact state and continued training
    mod_r = _fit_module(monkeypatch, "0", steps=0)
    assert mod_r._fused._ts.zero is False
    mod_r.load_optimizer_states(st_z)
    blob_r = pickle.loads(mod_r._fused.get_states())
    assert blob_r["step"] == blob_z["step"]
    for k in blob_z["opt_state"]:
        np.testing.assert_array_equal(
            np.asarray(blob_r["opt_state"][k]),
            np.asarray(blob_z["opt_state"][k]), err_msg=k)

    # and the reverse direction: replicated save -> zero=True restore
    st_r = str(tmp_path / "repl.states")
    mod_r.save_optimizer_states(st_r)
    mod_z2 = _fit_module(monkeypatch, "1", steps=0)
    mod_z2.load_optimizer_states(st_r)
    blob_z2 = pickle.loads(mod_z2._fused.get_states())
    for k in blob_z["opt_state"]:
        np.testing.assert_array_equal(
            np.asarray(blob_z2["opt_state"][k]),
            np.asarray(blob_z["opt_state"][k]), err_msg=k)


def test_zero_knob_validation(monkeypatch):
    """MXNET_TPU_ZERO* knobs are strictly validated at the read site
    (PR 6 convention): nonsense raises instead of silently defaulting."""
    sym = _uneven_symbol()
    opt = functional_optimizer("sgd")
    for knob, bad in [("MXNET_TPU_ZERO", "banana"),
                      ("MXNET_TPU_ZERO_WIRE", "3bit"),
                      ("MXNET_TPU_ZERO_MIN_SIZE", "-4"),
                      ("MXNET_TPU_ZERO_WIRE_THRESHOLD", "nope")]:
        monkeypatch.setenv(knob, bad)
        with pytest.raises(MXNetError, match=knob):
            TrainStep(sym, opt, mesh=make_mesh({"dp": 8}))
        monkeypatch.delenv(knob)
    with pytest.raises(MXNetError, match="zero_wire"):
        TrainStep(sym, opt, mesh=make_mesh({"dp": 8}), zero_wire="3bit")
    # all registered in the knob table (discoverable via describe())
    from mxnet_tpu import config

    for knob in ("MXNET_TPU_ZERO", "MXNET_TPU_ZERO_WIRE",
                 "MXNET_TPU_ZERO_WIRE_THRESHOLD",
                 "MXNET_TPU_ZERO_MIN_SIZE", "MXNET_TPU_ZERO_SERVER"):
        assert knob in config.KNOBS


@pytest.mark.slow
def test_zero_tp_params_keep_mirrored_state():
    """A tensor-parallel-sharded param is excluded from the zero plan —
    its optimizer state keeps mirroring the param's tp sharding."""
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh({"dp": 4, "tp": 2})
    rules = [(r"fc1_weight$", P("tp", None))]
    ts = TrainStep(get_symbol("mlp", num_classes=16),
                   functional_optimizer("sgd", momentum=0.9),
                   mesh=mesh, zero=True, zero_min_size=8,
                   param_rules=rules)
    params, st, aux = ts.init_params({"data": (8, 32),
                                      "softmax_label": (8,)})
    carry = ts.place(params, st, aux)
    batch = {"data": np.zeros((8, 32), np.float32),
             "softmax_label": np.zeros((8,), np.float32)}
    carry, loss = ts(carry, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(loss))
    assert "fc1_weight" not in ts.zero_plan(carry[0])
    assert carry[1]["fc1_weight"].sharding.spec == P("tp", None)
    # a replicated param of the same graph still shards its state over
    # the data axes (dp only — tp is not a data axis)
    assert "fc2_weight" in ts.zero_plan(carry[0])
    assert carry[1]["fc2_weight"].sharding.spec == P(("dp",), None)
