"""ISSUE 26 — one span primitive: ``profiler.span`` in the fit loop, the
generate broker and the jitted steps, with the counters recorded at the same
boundaries.  CPU, tiny sizes; the Chrome events stand in for the trace's host
plane (``span`` writes both from the same enter and exit)."""
import ast
import gc
import inspect
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.serving import GenerateServer


@pytest.fixture(autouse=True)
def _clean():
    profiler.profiler_set_state("stop")
    profiler.profiler_set_config(mode="symbolic", filename="profile.json")
    profiler._STATE["events"] = []
    profiler.generate_reset()
    profiler.pipeline_reset()
    yield
    profiler.profiler_set_state("stop")
    profiler.profiler_set_config(mode="symbolic", filename="profile.json")
    profiler._STATE["events"] = []


def _recorded(fn):
    """The Chrome events of ``fn()`` under ``profiler_set_state('run')``."""
    profiler.profiler_set_state("run")
    try:
        fn()
    finally:
        profiler.profiler_set_state("stop")
    return list(profiler._STATE["events"])


def _inside(child, parent):
    # the events' times are whole microseconds, each rounded down
    return (child["tid"] == parent["tid"] and parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1)


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------
def test_span_off_records_nothing_and_is_the_bare_annotation():
    from jax.profiler import TraceAnnotation

    with profiler.span("mx.fit.batch", epoch=0, nbatch=0) as s:
        assert type(s) is TraceAnnotation
    assert profiler._STATE["events"] == []


def test_span_on_writes_one_chrome_event_with_its_arguments():
    def body():
        with profiler.span("mx.serve.admit") as s:
            s.set_metadata(admitted=2)
        with profiler.span("mx.fit.h2d", name="data", nbytes=64):
            pass

    admit, h2d = _recorded(body)
    assert admit["name"] == "mx.serve.admit" and admit["cat"] == "admit"
    assert admit["args"] == {"admitted": 2}
    assert h2d["args"] == {"name": "data", "nbytes": 64}
    for e in (admit, h2d):
        assert e["ph"] == "X" and e["dur"] >= 0
        assert e["tid"] == threading.get_ident()


def test_one_span_system_is_left():
    for gone in ("maybe_scope", "scope", "record_event"):
        assert not hasattr(profiler, gone), gone
    # every span the program opens is a row of the docstring's table
    doc = profiler.__doc__
    import mxnet_tpu.executor
    import mxnet_tpu.metric
    import mxnet_tpu.module.base_module
    import mxnet_tpu.module.spmd_group
    import mxnet_tpu.ndarray.ndarray
    import mxnet_tpu.parallel.feed
    import mxnet_tpu.serving.broker

    opened = set()
    for mod in (mxnet_tpu.executor, mxnet_tpu.metric,
                mxnet_tpu.module.base_module, mxnet_tpu.module.spmd_group,
                mxnet_tpu.ndarray.ndarray, mxnet_tpu.parallel.feed,
                mxnet_tpu.serving.broker):
        for node in ast.walk(ast.parse(inspect.getsource(mod))):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "span"):
                assert isinstance(node.args[0], ast.Constant), \
                    "span names are fixed strings (%s)" % mod.__name__
                opened.add(node.args[0].value)
    assert len(opened) >= 25
    for name in opened:
        assert name.startswith("mx.") and "``%s``" % name in doc, name


def test_imperative_path_imports_nothing_per_call():
    from mxnet_tpu.ndarray import ndarray as nd_mod

    invoke = next(f for f in vars(nd_mod).values()
                  if inspect.isfunction(f)
                  and "all_operators" in inspect.getsource(f))
    # what is left are the two lazy imports of the symbolic-tracing branch
    imported = {a.name for node in ast.walk(ast.parse(inspect.getsource(invoke)))
                if isinstance(node, (ast.Import, ast.ImportFrom))
                for a in node.names}
    assert imported == {"Symbol", "create_symbol"}
    # symbolic mode stamps no operator, mode="all" stamps each by its name
    events = _recorded(lambda: mx.nd.relu(mx.nd.array(np.ones((2, 2)))))
    assert [e for e in events if e["cat"] == "operator"] == []
    profiler.profiler_set_config(mode="all")
    events = _recorded(lambda: mx.nd.relu(mx.nd.array(np.ones((2, 2)))))
    assert "relu" in {e["name"] for e in events if e["cat"] == "operator"}


# ---------------------------------------------------------------------------
# the fit loop
# ---------------------------------------------------------------------------
def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _two_batches():
    rng = np.random.RandomState(0)
    X = rng.randn(128, 8).astype(np.float32)
    y = rng.randint(0, 4, 128).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=64, shuffle=False)
    return it, mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])


def _fit_two_batches(eval_metric, callback):
    it, mod = _two_batches()
    mod.fit(it, num_epoch=1, kvstore="tpu", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1}, eval_metric=eval_metric,
            initializer=mx.initializer.Xavier(),
            batch_end_callback=callback)
    assert mod._fused is not None


def test_fit_spans_nest_in_order_on_the_device_metrics_path():
    def read(param):                       # as Speedometer: a blocking get
        param.eval_metric.get()

    events = _recorded(lambda: _fit_two_batches(mx.metric.Accuracy(), read))
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    batches = sorted(by["mx.fit.batch"], key=lambda e: e["ts"])
    assert [b["args"] for b in batches] == [{"epoch": 0, "nbatch": 0},
                                            {"epoch": 0, "nbatch": 1}]
    order = ["mx.fit.forward_backward", "mx.fit.update", "mx.fit.next_batch",
             "mx.fit.update_metric", "mx.fit.callbacks"]
    main = threading.get_ident()
    for b in batches:
        children = [next(e for e in by[name] if _inside(e, b))
                    for name in order]
        starts = [c["ts"] for c in children]
        assert starts == sorted(starts)
        fb, next_batch, callbacks = children[0], children[2], children[-1]
        # fit feeds the fused step through the device queue (ISSUE 27): the
        # step copies nothing, and the loop waits for the worker's batch
        assert not any(_inside(e, fb) for e in by["mx.fit.h2d"])
        dispatch = [e for e in by["mx.fit.dispatch"] if _inside(e, fb)]
        assert len(dispatch) == 1
        waits = [e for e in by["mx.fit.feed_wait"] if _inside(e, next_batch)]
        assert len(waits) == 1
        assert any(_inside(e, callbacks) for e in by["mx.metric.drain"])
    # the copies are the worker's, one thread, two arrays a batch, each
    # batch's before the dispatch of the step that takes it
    h2d = sorted(by["mx.fit.h2d"], key=lambda e: e["ts"])
    assert len({e["tid"] for e in h2d}) == 1 and h2d[0]["tid"] != main
    assert [e["args"]["name"] for e in h2d] == ["data", "softmax_label"] * 2
    assert all(e["args"]["nbytes"] > 0 for e in h2d)
    dispatches = sorted(by["mx.fit.dispatch"], key=lambda e: e["ts"])
    for i, d in enumerate(dispatches):
        assert h2d[2 * i + 1]["ts"] + h2d[2 * i + 1]["dur"] <= d["ts"]
    # the epoch's first next() waits before the first batch's span opens,
    # the last one finds the end of the epoch
    assert len(by["mx.fit.feed_wait"]) == 3
    assert {e["tid"] for e in by["mx.fit.feed_wait"]} == {main}
    steps = sorted(e["args"]["step"] for e in by["mx.fit.dispatch"])
    assert steps == [steps[0], steps[0] + 1]
    assert by["mx.fit.epoch_end"][0]["args"] == {"epoch": 0}
    assert "mx.fit.host_sync" not in by

    pipe = profiler.pipeline_stats()
    assert pipe["host_syncs"] == 0          # the ISSUE 5 number keeps its meaning
    assert pipe["metric_drains"] == 2       # and this one says the loop blocked
    assert pipe["sync_seconds"] > 0
    drains = sum(e["dur"] for e in by["mx.metric.drain"]) / 1e6
    assert pipe["sync_seconds"] == pytest.approx(drains, abs=0.05)


def test_explicit_forward_backward_on_host_batches_copies_in_place():
    """Outside ``fit`` the step still slices and copies the host batch it
    is given, on the caller's thread, before it dispatches."""
    it, mod = _two_batches()
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(kvstore="tpu", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    assert mod._fused is not None

    def drive():
        for batch in it:
            with profiler.span("mx.fit.forward_backward"):
                mod.forward_backward(batch)
            mod.update()

    events = _recorded(drive)
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    assert len(by["mx.fit.forward_backward"]) == 2
    for fb in by["mx.fit.forward_backward"]:
        h2d = [e for e in by["mx.fit.h2d"] if _inside(e, fb)]
        assert sorted(e["args"]["name"] for e in h2d) == ["data",
                                                         "softmax_label"]
        dispatch = [e for e in by["mx.fit.dispatch"] if _inside(e, fb)]
        assert len(dispatch) == 1
        assert max(e["ts"] + e["dur"] for e in h2d) <= dispatch[0]["ts"]
    assert "mx.fit.feed_wait" not in by
    assert profiler.pipeline_stats()["preplaced"] == 0


def test_fit_host_fallback_path_blocks_in_host_sync_every_batch():
    # top-k has no in-step statistics: the metric needs the real arrays
    events = _recorded(
        lambda: _fit_two_batches(mx.metric.TopKAccuracy(top_k=2), None))
    syncs = [e for e in events if e["name"] == "mx.fit.host_sync"]
    updates = [e for e in events if e["name"] == "mx.fit.update_metric"]
    assert len(updates) == 2
    for u in updates:
        assert any(_inside(s, u) for s in syncs)
    pipe = profiler.pipeline_stats()
    assert pipe["host_syncs"] == 2 and pipe["metric_drains"] == 0
    assert pipe["sync_seconds"] > 0


# ---------------------------------------------------------------------------
# the generate broker
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def model():
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64, max_len=64, dtype="float32")
    return cfg, tfm.init_params(cfg, seed=0)


def test_broker_spans_and_counters_for_three_requests(model):
    cfg, params = model
    results, streamed = [], []

    def serve():
        with GenerateServer(cfg, params, slots=2, page_size=8, max_steps=16,
                            stream_flush=1, name="tspans") as srv:
            futures = [srv.submit(np.arange(1, 6 + 3 * i), max_new_tokens=4 + i,
                                  stream_fn=streamed.extend)
                       for i in range(3)]
            results.extend(f.result(timeout=60) for f in futures)

    events = _recorded(serve)
    rids = [r["rid"] for r in results]
    assert rids == sorted(rids) and len(set(rids)) == 3
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)

    for name in ("mx.serve.submit", "mx.serve.prefill", "mx.serve.finish"):
        assert sorted(e["args"]["rid"] for e in by[name]) == rids, name
    client = threading.get_ident()
    assert {e["tid"] for e in by["mx.serve.submit"]} == {client}
    worker = {e["tid"] for e in by["mx.serve.loop"]}
    assert len(worker) == 1 and client not in worker
    for e in by["mx.serve.prefill"]:
        a = e["args"]
        assert a["bucket"] >= a["prompt_tokens"] and a["prefix_len"] == 0
        assert a["queue_wait_ms"] >= 0 and 0 <= a["slot"] < 2
        assert any(_inside(d, e) for d in by["mx.serve.prefill.device"])
    for e in by["mx.serve.finish"]:
        out = next(r for r in results if r["rid"] == e["args"]["rid"])
        assert e["args"]["reason"] == out["finish_reason"] == "length"
        assert e["args"]["tokens"] == len(out["tokens"])
    # a turn of the decode loop (``mx.serve.decode.device``) dispatches a step
    # for ``active`` slots and reads the ids of the one dispatched a turn
    # before: every step run is in one dispatch span and one read span of the
    # same ``step``, the read after the dispatch, in the same turn or a later
    assert "mx.serve.decode_step" not in by
    turns = sorted(by["mx.serve.decode.device"], key=lambda e: e["ts"])
    steps = by["mx.serve.decode.dispatch"]
    dispatched = {e["args"]["step"]: e for e in steps}
    read = {e["args"]["step"]: e for e in by["mx.serve.decode.read"]}
    assert len(dispatched) == len(steps)
    assert len(read) == len(by["mx.serve.decode.read"])
    assert sorted(dispatched) == sorted(read) == list(range(len(steps)))

    def turn_of(e):
        (i,) = [i for i, t in enumerate(turns) if _inside(e, t)]
        return i

    for step, d in dispatched.items():
        r = read[step]
        assert 1 <= d["args"]["active"] <= 2
        assert d["ts"] + d["dur"] <= r["ts"] and turn_of(d) <= turn_of(r)
    for i, t in enumerate(turns):
        assert any(turn_of(e) == i for e in steps + by["mx.serve.decode.read"])
        assert any(_inside(t, turn) for turn in by["mx.serve.loop"])
    assert len(by["mx.serve.decode.sample"]) == len(read)
    # one span more a turn than a turn spanned before (decode_step, device,
    # sample): dispatch, read, and the device and sample around them
    assert len(steps) + len(read) <= 2 * len(turns)
    assert sum(e["args"]["admitted"] for e in by["mx.serve.admit"]) == 3
    assert len(steps) <= len(by["mx.serve.grow_pages"]) <= len(turns)

    st = profiler.generate_stats()
    assert st["decode_steps"] == len(steps) and st["prefills"] == 3
    assert 0 < st["decode_steps_ahead"] < st["decode_steps"]
    assert st["decode_tokens_discarded"] == 0
    assert st["prefill_seconds"] > 0 and st["decode_seconds"] > 0
    assert st["prefill_seconds"] + st["decode_seconds"] == st["busy_seconds"]
    assert st["loop_seconds"] >= st["busy_seconds"]
    assert st["loop_host_ms_per_step"] >= 0
    assert st["queue_wait_count"] == 3
    assert 0 <= st["queue_wait_p50_ms"] <= st["queue_wait_p95_ms"]
    # two slots, three requests: the third is prefilled between decode steps
    assert 1 <= st["decode_steps_after_prefill"] <= st["decode_steps"]
    assert 0 < st["decode_after_prefill_share"] <= 1
    assert st["prefill_ms_avg"] == pytest.approx(
        1e3 * st["prefill_seconds"] / 3)
    assert len(streamed) == sum(len(r["tokens"]) for r in results)
    assert 0 < st["stream_seconds"] < st["loop_seconds"]


def test_idle_broker_waits_in_spans_of_a_slice_and_wakes_on_submit(model, monkeypatch):
    from mxnet_tpu.serving import broker

    cfg, params = model
    prompt = np.arange(1, 9)
    slice_us = 1e6 * broker._WAIT_SLICE_S

    def serve():
        with GenerateServer(cfg, params, slots=2, page_size=8, max_steps=16,
                            name="twait") as srv:
            srv.submit(prompt, max_new_tokens=2).result(timeout=60)  # compiled
            time.sleep(0.4)
            # a slice far longer than the test: only the notify can end it
            monkeypatch.setattr(broker, "_WAIT_SLICE_S", 300.0)
            time.sleep(0.5)
            t0 = time.perf_counter()
            srv.submit(prompt, max_new_tokens=2).result(timeout=60)
            waited.append(time.perf_counter() - t0)

    waited = []
    events = _recorded(serve)
    assert waited[0] < 30
    waits = [e for e in events if e["name"] == "mx.serve.wait_work"]
    loop = {e["tid"] for e in events if e["name"] == "mx.serve.loop"}
    assert {e["tid"] for e in waits} == loop
    # 0.4 s idle in slices of 50 ms, then the long slice the submit ended
    short = [e for e in waits if e["dur"] < 2e5]
    assert len(short) >= 5 and len(waits) - len(short) == 1
    assert max(e["dur"] for e in short) <= slice_us + 25e3


def test_a_collection_is_one_span_with_its_generation():
    gc.disable()
    try:
        events = _recorded(lambda: gc.collect(1))
    finally:
        gc.enable()
    (e,) = [e for e in events if e["name"] == "mx.host.gc"]
    assert e["args"]["generation"] == 1 and e["args"]["collected"] >= 0
    assert e["tid"] == threading.get_ident() and e["cat"] == "gc"
    assert profiler._GC_SPAN == [None]


def test_generate_record_still_refuses_unknown_names():
    with pytest.raises(ValueError, match="unknown counter"):
        profiler.generate_record(busy_seconds=1.0)
    with pytest.raises(ValueError, match="unknown counter"):
        profiler.generate_record(queue_seconds=1.0)
    profiler.generate_record(queue_waits=[0.010, 0.030], prefills=1,
                             prefill_seconds=0.5)
    st = profiler.generate_stats(reset=True)
    assert st["queue_wait_count"] == 2 and st["busy_seconds"] == 0.5
    assert profiler.generate_stats() == {}


# ---------------------------------------------------------------------------
# the jitted steps and the kernels
# ---------------------------------------------------------------------------
def _op_names(lowered):
    return lowered.as_text(debug_info=True)


def test_lm_train_step_carries_the_scope_names(model):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.mesh import train_mesh

    cfg, params = model
    step, place = tfm.make_train_step(cfg, train_mesh(jax.devices()[:1], mp=1))
    tokens = jnp.zeros((2, 17), jnp.int32)
    text = _op_names(step.lower(place(params), tokens))
    for scope in ("mx.lm.embed", "mx.lm.attn", "mx.lm.ffn", "mx.lm.head_loss",
                  "mx.opt.update"):
        assert scope in text, scope


def test_generate_programs_carry_the_scope_names(model):
    import jax
    import jax.numpy as jnp

    cfg, params = model
    cache = tfm.init_kv_cache(cfg, num_pages=8, page_size=8)
    decode = jax.jit(tfm.make_decode_fn(cfg, 2, 4, 8))
    text = _op_names(decode.lower(
        params, cache, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), bool)))
    for scope in ("mx.gen.pool_write", "mx.gen.gather_kv", "mx.gen.attn",
                  "mx.lm.ffn"):
        assert scope in text, scope
    prefill = jax.jit(tfm.make_prefill_fn(cfg, 8))
    text = _op_names(prefill.lower(
        params, cache, jnp.zeros((1, 16), jnp.int32), jnp.int32(9),
        jnp.zeros((2,), jnp.int32)))
    for scope in ("mx.gen.pool_write", "mx.gen.attn"):
        assert scope in text, scope


def test_decode_through_the_kernel_names_it_and_gathers_nothing(model, monkeypatch):
    """On a TPU the decode program attends the pool in place: the scope
    ``mx.gen.gather_kv`` stays only where a gather does."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(tfm, "kernel_platform", lambda: "tpu")
    cfg, params = model
    cache = tfm.init_kv_cache(cfg, num_pages=8, page_size=8)
    decode = jax.jit(tfm.make_decode_fn(cfg, 2, 4, 8))
    text = _op_names(decode.lower(
        params, cache, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), bool)))
    for scope in ("mx.gen.pool_write", "mx.gen.attn", "mx_paged_decode",
                  "mx.lm.ffn"):
        assert scope in text, scope
    assert "mx.gen.gather_kv" not in text


def test_latent_expert_programs_carry_their_scope_names():
    """ISSUE 36: the scopes the decode-pool cell's per-layer metrics read."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import mla_moe

    cfg = mla_moe.LatentMoEConfig(
        vocab=32, d_model=32, n_heads=2, n_layers=2, n_dense_layers=1, d_ff=48,
        d_expert=16, n_experts=8, experts_per_token=2, held_experts=(0, 1),
        q_rank=16, kv_rank=8, d_nope=4, d_rope=4, d_v=8, index_heads=2, index_dim=8,
        index_rope_dim=4, index_topk=4, max_len=32, dtype="float32")
    params = mla_moe.init_params(cfg)
    cache = mla_moe.init_kv_cache(cfg, num_pages=8, page_size=4)
    decode = jax.jit(mla_moe.make_decode_fn(cfg, 2, 4, 4))
    text = _op_names(decode.lower(
        params, cache, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), bool)))
    scopes = ("mx.gen.latent_proj", "mx.gen.index", "mx.gen.attn", "mx.gen.pool_write",
              "mx.lm.moe.route", "mx.lm.moe.experts", "mx.lm.moe.shared", "mx.lm.ffn")
    for scope in scopes:
        assert scope in text, scope
    assert "mx.gen.gather_kv" not in text
    prefill = jax.jit(mla_moe.make_prefill_fn(cfg, 4))
    text = _op_names(prefill.lower(
        params, cache, jnp.zeros((1, 8), jnp.int32), jnp.int32(5),
        jnp.zeros((2,), jnp.int32)))
    for scope in scopes:
        assert scope in text, scope


def test_double_block_programs_carry_their_scope_names():
    """ISSUE 38: the scopes ``longcat-flash.decode-pool-12k``'s metrics read."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import scmoe

    cfg = scmoe.ShortcutMoEConfig(
        vocab=32, d_model=32, n_heads=2, n_layers=1, d_ff=48, d_expert=16, n_experts=8,
        n_zero_experts=4, experts_per_token=2, held_experts=(0, 1), q_rank=16, kv_rank=8,
        d_nope=4, d_rope=4, d_v=4, max_len=32, dtype="float32")
    params = scmoe.init_params(cfg)
    cache = scmoe.init_kv_cache(cfg, num_pages=8, page_size=4)
    decode = jax.jit(scmoe.make_decode_fn(cfg, 2, 4, 4))
    text = _op_names(decode.lower(
        params, cache, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), bool)))
    scopes = ("mx.gen.latent_proj", "mx.gen.attn", "mx.gen.pool_write", "mx.lm.ffn",
              "mx.lm.moe.route", "mx.lm.moe.experts", "mx.lm.moe.zero")
    for scope in scopes:
        assert scope in text, scope
    assert "mx.gen.index" not in text and "mx.lm.moe.shared" not in text
    prefill = jax.jit(scmoe.make_prefill_fn(cfg, 4))
    text = _op_names(prefill.lower(
        params, cache, jnp.zeros((1, 8), jnp.int32), jnp.int32(5),
        jnp.zeros((2,), jnp.int32)))
    for scope in scopes:
        assert scope in text, scope


def test_hyper_connected_programs_carry_their_scope_names():
    """The hyper-connections' maps under ``mx.lm.hc`` in prefill and
    decode; the gate's product under ``mx.gen.latent_proj``, its multiply and
    the sink under ``mx.gen.attn``; the indexer's scope only where a layer
    has one of its own; the reuse counted beside GLM-5's six counters."""
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import mla_moe

    cfg = mla_moe.LatentMoEConfig(
        vocab=32, d_model=32, n_heads=2, n_layers=3, n_dense_layers=1, d_ff=48,
        d_expert=16, n_experts=8, experts_per_token=2, held_experts=(0, 1),
        q_rank=16, kv_rank=8, d_nope=4, d_rope=4, d_v=8, index_heads=2, index_dim=8,
        index_rope_dim=4, index_topk=4, indexer_types=("full", "shared", "shared"),
        hc_mult=4, attn_gate=True, attn_sink=True, swiglu_limit=10.0, head_fp32=True,
        max_len=32, dtype="float32")
    params = mla_moe.init_params(cfg)
    cache = mla_moe.init_kv_cache(cfg, num_pages=8, page_size=4)
    decode = jax.jit(mla_moe.make_decode_fn(cfg, 2, 4, 4))
    prefill = jax.jit(mla_moe.make_prefill_fn(cfg, 4))
    texts = [_op_names(decode.lower(
        params, cache, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), bool))), _op_names(prefill.lower(
            params, cache, jnp.zeros((1, 8), jnp.int32), jnp.int32(5),
            jnp.zeros((2,), jnp.int32)))]
    for text in texts:
        for scope in ("mx.lm.hc", "mx.gen.latent_proj", "mx.gen.index", "mx.gen.attn",
                      "mx.gen.pool_write", "mx.lm.moe.experts", "mx.lm.ffn"):
            assert scope in text, scope
        # the sink's exp and the gate's multiply under attention, the gate's
        # sigmoid beside its product
        assert re.search(r"mx\.gen\.latent_proj/logistic", text)
    assert mla_moe.decode_counters(cfg) == mla_moe.INDEXED_DECODE_COUNTERS + (
        "dsa_selections_reused",)
    profiler.generate_record(dsa_selections_reused=4)
    assert profiler.generate_stats(reset=True)["dsa_selections_reused"] == 4


def test_double_block_counters_ride_generate_stats():
    from mxnet_tpu.models import scmoe

    profiler.generate_record(**{k: 3 for k in scmoe.DECODE_COUNTERS})
    st = profiler.generate_stats(reset=True)
    assert st["moe_pairs_zero"] == 3 and st["attn_rows_read"] == 3
    with pytest.raises(ValueError):
        profiler.generate_record(moe_pairs_nought=1)


def test_device_counters_of_a_decode_step_ride_generate_stats():
    from mxnet_tpu.models import mla_moe

    names = mla_moe.decode_counters(mla_moe.LatentMoEConfig())
    profiler.generate_record(**{k: 2 for k in names})
    profiler.generate_record(moe_pairs_held=6, moe_pairs_at_max_load=10)
    st = profiler.generate_stats(reset=True)
    assert all(st[k] >= 2 for k in names)
    # 8 pairs on held experts; 12 were every one as full as the fullest
    assert st["moe_expert_load_max_over_mean"] == pytest.approx(12 / 8)
    profiler.generate_record(decode_steps=1)
    assert "moe_expert_load_max_over_mean" not in profiler.generate_stats(reset=True)


def test_symbolic_train_step_tells_forward_backward_and_update_apart():
    import jax

    from mxnet_tpu.parallel.spmd import TrainStep, functional_optimizer

    ts = TrainStep(_mlp(), functional_optimizer("sgd", learning_rate=0.1))
    params, opt_state, aux = ts.init_params({"data": (8, 8),
                                             "softmax_label": (8,)})
    carry = ts.place(params, opt_state, aux)
    batch = {"data": np.zeros((8, 8), np.float32),
             "softmax_label": np.zeros((8,), np.float32)}
    fn = ts.compile(*carry[:3])
    text = _op_names(fn.lower(carry, batch, jax.random.PRNGKey(0)))
    for scope in ("mx.step.forward", "mx.step.backward", "mx.opt.update"):
        assert scope in text, scope


def test_flash_kernels_are_named_in_the_lowered_step():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import flash_attention

    q = jnp.ones((1, 2, 32, 16), jnp.float32)

    def loss(q):
        return jnp.sum(flash_attention(q, q, q, causal=True, interpret=True))

    jaxpr = str(jax.make_jaxpr(jax.grad(loss))(q))
    for name in ("mx_flash_fwd", "mx_flash_dq", "mx_flash_dkv"):
        assert name in jaxpr, name
