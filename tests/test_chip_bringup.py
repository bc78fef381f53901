"""Chip bring-up contract (ISSUE 21): nothing on the main path may hide
which device it runs on. Fast, CPU-only: the chip itself is reached only
through ``chip_smoke.py`` under the builder's chip tool."""
import json
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx
from mxnet_tpu import context as ctx_mod
from mxnet_tpu.base import MXNetError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def test_chip_smoke_refuses_cpu_before_building_a_model():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, SMOKE], env=env,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode not in (0, None)
    assert "platform is 'cpu'" in proc.stderr
    assert "nothing was built" in proc.stderr
    # the header names what it found; no phase ran, no result line
    assert proc.stdout.startswith("platform=cpu ")
    assert "== phase" not in proc.stdout
    assert '"ok"' not in proc.stdout


def test_chip_smoke_result_line_is_exactly_ok_and_device(capsys):
    """The driver parses the last stdout line and refuses any other key
    in it; the rich summary is the line before. A dry run ends with the
    summary and prints no result."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    for ok in (True, False):
        smoke.finish({"ok": False, "device": dev, "dry_run": False,
                      "phases": {}}, ok=ok)
        summary, result = map(json.loads,
                              capsys.readouterr().out.splitlines())
        assert result == {"ok": ok, "device": dev}
        assert summary["ok"] is ok and "phases" in summary
        assert list(summary)[-1] == "claim" and summary["claim"] is None
    smoke.finish({"device": dict(dev, platform="cpu"), "dry_run": True},
                 ok=True)
    (only,) = capsys.readouterr().out.splitlines()
    assert json.loads(only)["dry_run"] is True


class _FakeDevice:
    def __init__(self, platform, i):
        self.platform, self.id = platform, i

    def __repr__(self):
        return "%s:%d" % (self.platform, self.id)


def _fake_backends(monkeypatch, platforms, **devices):
    """Pretend this process sees ``devices`` per platform and that
    jax_platforms was set to ``platforms``."""
    monkeypatch.setattr(ctx_mod, "_DEVICE_CACHE", {
        name: tuple(_FakeDevice(name, i) for i in range(n))
        for name, n in dict({"cpu": 0, "gpu": 0, "tpu": 0},
                            **devices).items()})
    monkeypatch.setattr(ctx_mod, "_jax_platforms", lambda: platforms)


def test_tpu_context_past_the_chip_count_raises(monkeypatch):
    _fake_backends(monkeypatch, "tpu,cpu", tpu=1, cpu=1)
    assert mx.Context("tpu", 0).jax_device().platform == "tpu"
    with pytest.raises(MXNetError, match=r"tpu\(9\).*1 tpu device"):
        mx.Context("tpu", 9).jax_device()


def test_tpu_context_without_a_tpu_raises_unless_cpu_was_forced(monkeypatch):
    # JAX's own "TPU init failed, falling back to CPU": the platform list
    # still names tpu, only cpu devices exist — never a silent stand-in
    _fake_backends(monkeypatch, "tpu,cpu", cpu=1)
    with pytest.raises(MXNetError, match="0 tpu device"):
        mx.tpu(0).jax_device()
    _fake_backends(monkeypatch, None, cpu=1)
    with pytest.raises(MXNetError, match="0 tpu device"):
        mx.tpu(0).jax_device()
    # the one allowed substitution: the platform explicitly forced to cpu
    _fake_backends(monkeypatch, "cpu", cpu=8)
    assert mx.tpu(3).jax_device().id == 3
    with pytest.raises(MXNetError, match="8 tpu device"):
        mx.tpu(9).jax_device()


def test_kernel_platform_rejects_other_backends(monkeypatch):
    import jax

    assert ctx_mod.kernel_platform() == "cpu"
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(MXNetError, match="'gpu'"):
        ctx_mod.kernel_platform()


def test_compile_cache_dir_placement(monkeypatch):
    import jax

    before = jax.config.jax_compilation_cache_dir
    threshold = jax.config.jax_persistent_cache_min_compile_time_secs
    # the import already ran it: this suite forces cpu, so nothing was set
    assert before is None and threshold > 0
    assert ctx_mod.compile_cache_dir() is None
    try:
        monkeypatch.setattr(ctx_mod, "_jax_platforms", lambda: "tpu,cpu")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where/else")
        assert ctx_mod.compile_cache_dir() == "/some/where/else"
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        # scope names are metadata: a trace must not show a cached
        # executable's older ones (ISSUE 26)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = ctx_mod.compile_cache_dir()
        assert first == os.path.join(ROOT, ".jax_cache")
        assert ctx_mod.compile_cache_dir() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          threshold)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)


def test_import_places_the_compile_cache():
    """Servers and user scripts never call the helper: importing the
    package places the cache, before anything can compile. (The forced
    cpu case is this process: see the test above.)"""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    # no backend is initialised by the import, so naming tpu is harmless
    out = subprocess.run(
        [sys.executable, "-c", "import mxnet_tpu, jax; "
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, cwd=ROOT, timeout=120, check=True,
        env=dict(env, JAX_PLATFORMS="tpu,cpu")).stdout.strip()
    assert out == os.path.join(ROOT, ".jax_cache")


def _bound_fc_module(**kwargs):
    data = mx.sym.var("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=4, name="fc"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu(0), **kwargs)
    mod.bind(data_shapes=[("data", (2, 8))],
             label_shapes=[("softmax_label", (2,))])
    mod.init_params()
    return mod


def test_compute_dtype_is_the_modules_and_never_silently_fp32(monkeypatch):
    from mxnet_tpu.module import spmd_group

    mod = _bound_fc_module(compute_dtype="bfloat16")
    mod.init_optimizer(kvstore="tpu")
    assert mod._fused._ts.compute_dtype == "bfloat16"
    # the optimizer's multi_precision keeps the reference's meaning (fp32
    # masters for low-precision weights; nothing on fp32 weights)
    mod = _bound_fc_module()
    mod.init_optimizer(kvstore="tpu",
                       optimizer_params={"multi_precision": True})
    assert mod._fused._ts.compute_dtype is None
    # the per-executor path computes in fp32: asking for bf16 there raises
    mod = _bound_fc_module(compute_dtype="bfloat16")
    with pytest.raises(MXNetError, match="needs the fused train step"):
        mod.init_optimizer(kvstore="local")

    def refuse(*a, **k):
        raise MXNetError("fused SPMD step: optimizer has no mirror")

    monkeypatch.setattr(spmd_group, "FusedSPMDGroup", refuse)
    with pytest.raises(MXNetError, match="needs the fused train step"):
        mod.init_optimizer(kvstore="tpu", force_init=True)


def test_init_optimizer_reraises_untyped_fused_failure(monkeypatch):
    from mxnet_tpu.module import spmd_group

    def boom(*a, **k):
        raise RuntimeError("mesh construction failed")

    monkeypatch.setattr(spmd_group, "FusedSPMDGroup", boom)
    mod = _bound_fc_module()
    with pytest.raises(RuntimeError, match="mesh construction failed"):
        mod.init_optimizer(kvstore="tpu")
    # the typed refusal still falls back to the per-executor path
    def refuse(*a, **k):
        raise MXNetError("fused SPMD step: optimizer has no mirror")

    monkeypatch.setattr(spmd_group, "FusedSPMDGroup", refuse)
    mod.init_optimizer(kvstore="tpu", force_init=True)
    assert mod._fused is None


def test_sharded_prefill_cross_lowers_for_tpu(monkeypatch):
    """A Mosaic kernel cannot be partitioned by GSPMD: bound on an mp
    mesh, prefill must shard_map its attention or the TPU lowering raises
    (seen on the four-chip host). Lowering for the tpu platform happens
    on the CPU host, so this needs no chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    import mxnet_tpu.kernels  # noqa: F401  (loads kernels.flash_attention)
    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.parallel.mesh import train_mesh

    for name in ("mxnet_tpu.models.transformer",
                 "mxnet_tpu.kernels.flash_attention"):
        monkeypatch.setattr(sys.modules[name], "kernel_platform",
                            lambda: "tpu")
    cfg = tfm.TransformerConfig(vocab=256, d_model=128, n_heads=4,
                                n_layers=2, d_ff=256, max_len=64)
    mesh = train_mesh(devices=jax.devices()[:2], mp=2)
    specs = tfm.param_specs(cfg, mesh)
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                      sharding=NamedSharding(mesh, specs[k]))
              for k, v in jax.eval_shape(
                  lambda: tfm.init_params(cfg)).items()}
    cache = jax.eval_shape(lambda: tfm.init_kv_cache(cfg, 8, 16))
    cache = jax.ShapeDtypeStruct(
        cache.shape, cache.dtype,
        sharding=NamedSharding(mesh, tfm.kv_cache_spec(mesh)))
    args = (params, cache, jax.ShapeDtypeStruct((1, 32), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.int32))
    lowered = jax.jit(tfm.make_prefill_fn(cfg, 16, mesh=mesh)).trace(
        *args).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(tfm.make_prefill_fn(cfg, 16)).trace(*args).lower(
            lowering_platforms=("tpu",))


def test_sharded_decode_cross_lowers_for_tpu(monkeypatch):
    """The decode program's paged kernel under an mp mesh: ``shard_map``
    over the lanes of the heads each chip holds, as prefill does for
    flash; without the mesh the TPU lowering refuses to partition it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    import mxnet_tpu.kernels  # noqa: F401  (loads kernels.flash_attention)
    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.parallel.mesh import train_mesh

    for name in ("mxnet_tpu.models.transformer",
                 "mxnet_tpu.kernels.flash_attention"):
        monkeypatch.setattr(sys.modules[name], "kernel_platform",
                            lambda: "tpu")
    cfg = tfm.TransformerConfig(vocab=256, d_model=128, n_heads=4,
                                n_layers=2, d_ff=256, max_len=64)
    mesh = train_mesh(devices=jax.devices()[:2], mp=2)
    specs = tfm.param_specs(cfg, mesh)
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                      sharding=NamedSharding(mesh, specs[k]))
              for k, v in jax.eval_shape(
                  lambda: tfm.init_params(cfg)).items()}
    cache = jax.eval_shape(lambda: tfm.init_kv_cache(cfg, 8, 16))
    cache = jax.ShapeDtypeStruct(
        cache.shape, cache.dtype,
        sharding=NamedSharding(mesh, tfm.kv_cache_spec(mesh)))
    args = (params, cache, jax.ShapeDtypeStruct((2,), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.int32),
            jax.ShapeDtypeStruct((2, 4), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.bool_))
    lowered = jax.jit(tfm.make_decode_fn(cfg, 2, 4, 16, mesh=mesh)).trace(
        *args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert "tpu_custom_call" in text and "mx_paged_decode" in text
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(tfm.make_decode_fn(cfg, 2, 4, 16)).trace(*args).lower(
            lowering_platforms=("tpu",))


@pytest.mark.slow
def test_chip_smoke_dry_run_cpu_end_to_end():
    proc = subprocess.run([sys.executable, SMOKE, "--dry-run-cpu"],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["ok"] is True
    assert summary["dry_run"] is True and summary["platform"] == "cpu"
    assert all(p["ok"] for p in summary["phases"].values())
    assert set(summary["phases"]) == {"trainer", "transformer", "server"}
    assert list(summary)[-1] == "claim" and summary["claim"] is None
