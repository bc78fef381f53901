#!/usr/bin/env python3
"""Chip smoke: drive the main path once on the accelerator and check it.

One process, no arguments, full width of the models the repo supports
(depth as published for ResNet-50; the transformer at its own default
config), random weights from a seed:

1. trainer  — ResNet-50 / ImageNet shapes through ``Module.fit(...,
   kvstore='tpu')`` with bf16 compute, 256 images per chip, the same
   synthetic batch for every step;
2. transformer — ``make_train_step`` on ``train_mesh()`` at batch 8 x
   seq 2048 with the Pallas flash kernel (asserted from the lowered step),
   plus flash fwd/grads against the materialized-scores reference;
3. server  — ``GenerateServer`` answering 6 concurrent requests over three
   prefill buckets, the donated paged KV cache, prefill logits against
   ``make_forward_fn``.

On more than one chip the same phases run sharded (ResNet on dp=N, the
transformer on (dp=N/2, mp=2), the server on one mp=2 group) and every
chip must hold its shard.

Exit 0 only when every phase passed on a TPU. The last two stdout lines
are JSON objects: the summary (per-phase ``ok``, wall seconds split into
compile and run, versions, ``native_runtime``, ``"claim": null``), then the
result, ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count":
...}}`` with exactly those keys. Without a TPU the script exits 2 before
building a model and prints neither. ``--dry-run-cpu`` runs the same phases
at tiny sizes on the CPU backend as a pre-flight; it ends with the summary,
which says so, and prints no result line.
"""
import argparse
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# Flash-vs-reference tolerance, relative to the reference's largest
# magnitude. Inputs, outputs and all three gradients are bf16 (the dtype
# the train step runs the kernel in); both sides accumulate in fp32 under
# default_matmul_precision("highest"), so what separates them is the
# kernel rounding its results to bf16 — one part in 2**8 = 3.9e-3 of an
# element — and a different summation order. 1e-2 is 2.5 rounding steps.
FLASH_TOL = 1e-2
# Prefill-vs-forward logits: same weights, same bf16 math, but the prompt
# is padded to its bucket so the flash kernel tiles the sequence
# differently and bf16 activations round differently through 4 layers
# (residual stream, two norms, attention and ffn each). 3e-2 of the
# largest logit is ~8 bf16 rounding steps.
LOGITS_TOL = 3e-2

FULL = dict(
    resnet=dict(num_classes=1000, num_layers=50, image_shape=(3, 224, 224)),
    per_chip_batch=256, train_steps=8,
    transformer=dict(),                  # TransformerConfig() defaults
    tf_batch=8, tf_seq=2048, tf_steps=3,
    parity_shapes=((2, 8, 2048, 64), (1, 8, 16, 64)),
    slots=8, page_size=16, prompt_lens=(40, 700, 12, 40, 700, 12),
    new_tokens=32,
)
TINY = dict(
    resnet=dict(num_classes=10, num_layers=20, image_shape=(3, 32, 32)),
    per_chip_batch=4, train_steps=4,
    transformer=dict(vocab=256, d_model=64, n_heads=4, n_layers=2,
                     d_ff=128, max_len=128),
    tf_batch=8, tf_seq=64, tf_steps=3,
    parity_shapes=((1, 2, 64, 16), (1, 2, 16, 16)),
    slots=4, page_size=8, prompt_lens=(5, 20, 3, 5, 20, 3),
    new_tokens=4,
)

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileMeter:
    """Counts what JAX compiled: seconds spent tracing/lowering/compiling
    (a persistent-cache hit still pays trace + lower + retrieval), compile
    requests that went through the persistent cache, and how many of those
    it answered. requests - hits = programs compiled from scratch."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return (self.seconds, self.requests, self.hits)


def run_phase(name, fn, meter, summary):
    """Run one phase, record wall/compile/run seconds and its facts. A
    failure is recorded, the summary is printed with ok=false, and the
    exception propagates: nothing after a failed phase runs."""
    print("== phase %s" % name, flush=True)
    s0, r0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    rec = summary["phases"][name] = {"ok": False}
    try:
        rec.update(fn())
        rec["ok"] = True
    finally:
        wall = time.perf_counter() - t0
        s1, r1, h1 = meter.snapshot()
        # nested traces are counted by each enclosing jit, so compile_s
        # can overshoot the wall by a little on a phase that only compiles
        rec.update(wall_s=round(wall, 2), compile_s=round(s1 - s0, 2),
                   run_s=round(max(wall - (s1 - s0), 0.0), 2),
                   compile_requests=r1 - r0, cache_hits=h1 - h0,
                   compiled_new=(r1 - r0) - (h1 - h0))
        print("   %s" % json.dumps(rec), flush=True)
        if not rec["ok"]:
            finish(summary, ok=False)
    return rec


def finish(summary, ok):
    """The summary line, then — on a chip only — the result line: exactly
    ``ok`` and ``device``, nothing else, as the last line of stdout. A dry
    run found no accelerator, so it prints no result."""
    summary["ok"] = ok
    summary["claim"] = None          # always the last key: nothing claimed
    print(json.dumps(summary), flush=True)
    if not summary["dry_run"]:
        print(json.dumps({"ok": ok, "device": summary["device"]}), flush=True)


def assert_on(arrays, devices, what):
    """Every leaf is laid out over exactly ``devices``."""
    import jax

    want = set(devices)
    for leaf in jax.tree_util.tree_leaves(arrays):
        got = set(leaf.sharding.device_set)
        if got != want:
            raise AssertionError(
                "%s: an array lives on %s, expected %s"
                % (what, sorted(map(str, got)), sorted(map(str, want))))


def device_memory(arrays, devices):
    """Bytes of ``arrays`` each device holds, from their own shards, and
    what the device reports live right now. Every device must hold a
    share and report at least that much in use — ``bytes_in_use``, not
    the process-wide peak, which an earlier phase would satisfy."""
    import jax

    held = dict.fromkeys(devices, 0)
    for leaf in jax.tree_util.tree_leaves(arrays):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    live = []
    for d in devices:
        stats = d.memory_stats() or {}      # None on the cpu backend
        live.append(stats.get("bytes_in_use"))
        if held[d] == 0:
            raise AssertionError("%s holds no shard" % d)
        if d.platform == "tpu" and not (live[-1] or 0) >= held[d]:
            raise AssertionError(
                "%s reports %r bytes in use but should hold a %d-byte share"
                % (d, live[-1], held[d]))
    return {"held_bytes": [held[d] for d in devices], "bytes_in_use": live}


# ---------------------------------------------------------------------------
# phase 1: the headline trainer
# ---------------------------------------------------------------------------
def phase_trainer(sz, devices):
    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet
    from mxnet_tpu.parallel.feed import place_batch_array

    n = len(devices)
    batch = sz["per_chip_batch"] * n
    shape = tuple(sz["resnet"]["image_shape"])
    mx.random.seed(0)                    # the Xavier draw
    rng = np.random.RandomState(0)
    data = rng.randn(batch, *shape).astype(np.float32)
    label = rng.randint(0, sz["resnet"]["num_classes"],
                        (batch,)).astype(np.float32)
    # one epoch that repeats one batch: every optimizer step sees the same
    # images, so a step that does not update the weights cannot lower the
    # loss, and no sample below includes fit's epoch end (get_params /
    # set_params move every weight device -> host -> device)
    train = mx.io.ResizeIter(mx.io.NDArrayIter(data, label, batch_size=batch),
                             sz["train_steps"])
    sym = resnet.get_symbol(**sz["resnet"])
    mod = mx.mod.Module(sym, context=[mx.tpu(i) for i in range(n)],
                        compute_dtype="bfloat16")
    losses, step_s = [], []
    clock = [time.perf_counter()]

    def on_batch(param):
        losses.append(float(param.eval_metric.get()[1]))   # blocks: D2H
        param.eval_metric.reset()        # per-batch loss, as Speedometer
        now = time.perf_counter()
        step_s.append(now - clock[0])
        clock[0] = now

    mod.fit(train, num_epoch=1, kvstore="tpu", optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                              "wd": 1e-4},
            initializer=mx.initializer.Xavier(),
            eval_metric=mx.metric.CrossEntropy(),
            batch_end_callback=on_batch)

    group = mod._fused
    if group is None:
        raise AssertionError("kvstore='tpu' did not engage the fused step")
    if group._ts.compute_dtype != "bfloat16":
        raise AssertionError("fused step computes in %r, not bfloat16"
                             % (group._ts.compute_dtype,))
    assert_on(group._carry, devices, "trainer carry")
    assert_on(group._raw_outputs, devices, "trainer outputs")
    placed = place_batch_array(group.mesh, group._data_axes, False, "data",
                               data, sharding=group._batch_sharding)
    assert_on(placed, devices, "trainer batch")
    if len(losses) != sz["train_steps"]:
        raise AssertionError("ran %d steps, wanted %d"
                             % (len(losses), sz["train_steps"]))
    if not np.all(np.isfinite(losses)):
        raise AssertionError("non-finite loss: %r" % (losses,))
    if not losses[-1] < losses[0]:
        raise AssertionError("loss did not fall on a repeated batch: %r"
                             % (losses,))
    return {"mesh": dict(group.mesh.shape), "global_batch": batch,
            "losses": [round(v, 4) for v in losses],
            "step_s_after_compile": [round(v, 3) for v in step_s[2:]],
            "memory": device_memory(group._carry, devices)}


# ---------------------------------------------------------------------------
# phase 2: transformer train step + flash kernel
# ---------------------------------------------------------------------------
def _flash_parity(shape):
    """flash_attention vs parallel.ring.full_attention: forward and the
    three gradients, bf16 in and out, relative to the reference's max."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import flash_attention
    from mxnet_tpu.parallel.ring import full_attention

    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.float32)
                  .astype(jnp.bfloat16) for kk in keys)

    def loss_of(attend):
        def f(q, k, v):
            out = attend(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))
        return f

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def ref(q, k, v):
        return full_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), causal=True)

    with jax.default_matmul_precision("highest"):
        got = (jax.jit(flash)(q, k, v),) + jax.jit(
            jax.grad(loss_of(flash), argnums=(0, 1, 2)))(q, k, v)
        want = (jax.jit(ref)(q, k, v),) + jax.jit(
            jax.grad(loss_of(ref), argnums=(0, 1, 2)))(q, k, v)
    errs = {}
    for name, g, r in zip(("out", "dq", "dk", "dv"), got, want):
        g = np.asarray(g, np.float32)
        r = np.asarray(r, np.float32)
        if not np.all(np.isfinite(g)):
            raise AssertionError("flash %s %r: non-finite" % (name, shape))
        errs[name] = float(np.max(np.abs(g - r)) / np.max(np.abs(r)))
        if errs[name] > FLASH_TOL:
            raise AssertionError(
                "flash %s at %r differs from the reference by %.3e of its "
                "max (tolerance %.0e)" % (name, shape, errs[name], FLASH_TOL))
    return {k: float("%.2e" % e) for k, e in errs.items()}


def phase_transformer(sz, devices, on_tpu):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.parallel.mesh import train_mesh

    cfg = tfm.TransformerConfig(**sz["transformer"])
    mesh = train_mesh(devices=devices, mp=2 if len(devices) > 1 else 1)
    step, place = tfm.make_train_step(cfg, mesh)
    carry = place(tfm.init_params(cfg, seed=0))
    assert_on(carry[0], devices, "transformer params")
    rng = np.random.RandomState(1)
    tokens = jax.device_put(
        rng.randint(0, cfg.vocab, (sz["tf_batch"], sz["tf_seq"] + 1))
        .astype(np.int32), NamedSharding(mesh, P("dp", None)))

    mosaic = None
    if on_tpu:
        # the kernel really compiled: not interpret mode, not the reference
        mosaic = "tpu_custom_call" in step.lower(carry, tokens).as_text()
        if not mosaic:
            raise AssertionError("lowered train step has no Mosaic "
                                 "custom call (tpu_custom_call)")
    losses, step_s = [], []
    for _ in range(sz["tf_steps"]):
        t0 = time.perf_counter()
        carry, loss = step(carry, tokens)
        losses.append(float(loss))                           # blocks
        step_s.append(time.perf_counter() - t0)
    if not np.all(np.isfinite(losses)):
        raise AssertionError("non-finite loss: %r" % (losses,))
    if not losses[-1] < losses[0]:
        raise AssertionError("loss did not fall on repeated tokens: %r"
                             % (losses,))
    assert_on(carry[0], devices, "transformer params after steps")
    parity = {"x".join(map(str, s)): _flash_parity(s)
              for s in sz["parity_shapes"]}
    return {"mesh": dict(mesh.shape), "mosaic_custom_call": mosaic,
            "losses": [round(v, 4) for v in losses],
            "step_s_after_compile": [round(v, 3) for v in step_s[1:]],
            "flash_rel_err": parity, "flash_tol": FLASH_TOL,
            "memory": device_memory(carry, devices)}


# ---------------------------------------------------------------------------
# phase 3: the server answers a few requests
# ---------------------------------------------------------------------------
def _paged_decode_parity(cfg, slots, page_size, device):
    """kernels.paged_decode against _paged_decode_attention over the
    gathered pages, at the server's own geometry on ``device``: ragged
    lengths (one slot inactive, one ending on a page boundary, one
    starting a page, one full), block tables in shuffled page order."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels.paged_decode import paged_decode_attention
    from mxnet_tpu.models import transformer as tfm

    H, hd = cfg.n_heads, cfg.d_model
    pages_per_slot = cfg.max_len // page_size
    num_pages = slots * pages_per_slot
    rng = np.random.RandomState(3)
    tables = (rng.permutation(num_pages) + 1).reshape(slots, pages_per_slot)
    lengths = rng.randint(1, cfg.max_len + 1, (slots,))
    lengths[:4] = (0, 3 * page_size, 3 * page_size + 1, cfg.max_len)
    with jax.default_device(device):
        kq, kp = jax.random.split(jax.random.PRNGKey(11))
        pool = jax.random.normal(
            kp, (2, 2, num_pages + 1, page_size, hd), jnp.float32
        ).astype(jnp.bfloat16)
        q = jax.random.normal(kq, (slots, hd), jnp.float32).astype(jnp.bfloat16)
        tables, lengths = jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32)

        def ref(q, pool, tables, lengths):
            kg, vg = tfm._gather_pages(pool[1], tables, H)
            return tfm._paged_decode_attention(
                q.reshape(slots, H, 1, -1), kg, vg, lengths - 1, 128
            ).reshape(slots, hd)

        got = jax.jit(lambda *a: paged_decode_attention(
            a[0], a[1], jnp.int32(1), a[2], a[3], n_heads=H))(
                q, pool, tables, lengths)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref)(q, pool, tables, lengths)
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    if np.any(got[0] != 0):
        raise AssertionError("paged decode: an inactive slot's output is not zero")
    err = float(np.max(np.abs(got[1:] - want[1:])) / np.max(np.abs(want[1:])))
    if not np.isfinite(err) or err > FLASH_TOL:
        raise AssertionError(
            "paged decode differs from the gathered reference by %.3e of "
            "its max (tolerance %.0e)" % (err, FLASH_TOL))
    return float("%.2e" % err)


def phase_server(sz, devices):
    import jax

    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.parallel.mesh import train_mesh
    from mxnet_tpu.serving import GenerateServer

    cfg = tfm.TransformerConfig(**sz["transformer"])
    group = list(devices[:2]) if len(devices) > 1 else list(devices[:1])
    with jax.default_device(group[0]):
        params = tfm.init_params(cfg, seed=1)
    bind = {"mesh": train_mesh(devices=group, mp=2)} if len(group) > 1 \
        else {"device": group[0]}
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab, (n,)).astype(np.int32)
               for n in sz["prompt_lens"]]
    with GenerateServer(cfg, params, slots=sz["slots"],
                        page_size=sz["page_size"], **bind) as srv:
        pred = srv.predictor
        assert_on(pred._params, group, "server params")
        assert_on(pred._kv, group, "server kv cache")
        memory = device_memory((pred._params, pred._kv), group)
        buckets = sorted({pred.pick_bucket(len(p)) for p in prompts})
        if len(buckets) < 2:
            raise AssertionError("prompts span one prefill bucket: %r"
                                 % (buckets,))
        futures = [srv.submit(p, max_new_tokens=sz["new_tokens"])
                   for p in prompts]
        results = [f.result(timeout=900) for f in futures]
        for p, r in zip(prompts, results):
            if r["finish_reason"] != "length" or \
                    len(r["tokens"]) != sz["new_tokens"]:
                raise AssertionError(
                    "request with %d prompt tokens finished %r after %d "
                    "tokens" % (len(p), r["finish_reason"], len(r["tokens"])))
        stats = srv.stats()
        if stats["pages_in_use"] != 0:
            raise AssertionError("%d KV pages still in use after the drain"
                                 % stats["pages_in_use"])
        # one more prefill through the (donated) cache against the
        # one-shot forward, on the same device(s)
        prompt = prompts[0]
        pages = pred.pool.alloc(pred.pages_needed(len(prompt)))
        try:
            got = pred.prefill(prompt, pages)
            # and one decode step through the same cache: the prompt's last
            # token again at its own position, so the step rewrites the row
            # prefill wrote and must give the same logits
            table = np.zeros((pred.slots, pred.max_pages_per_slot), np.int32)
            table[0, :len(pages)] = pages
            feed = np.zeros((pred.slots,), np.int32)
            feed[0] = prompt[-1]
            at = np.zeros((pred.slots,), np.int32)
            at[0] = len(prompt) - 1
            stepped = pred.decode(feed, at, table,
                                  np.arange(pred.slots) == 0)[0]
        finally:
            pred.pool.free(pages)
        # the reference runs unsharded on the group's first device
        want = np.asarray(tfm.make_forward_fn(cfg)(
            params, prompt[None, :]))[0, -1]
        errs = {}
        for name, logits in (("prefill", got), ("decode", stepped)):
            errs[name] = float(np.max(np.abs(logits - want))
                               / np.max(np.abs(want)))
            if not np.isfinite(errs[name]) or errs[name] > LOGITS_TOL:
                raise AssertionError(
                    "%s logits differ from make_forward_fn by %.3e of the "
                    "largest logit (tolerance %.0e)"
                    % (name, errs[name], LOGITS_TOL))
        sharded = pred.sharded_stats() if len(group) > 1 else None
        decode_parity = _paged_decode_parity(cfg, sz["slots"],
                                             sz["page_size"], group[0])
        if srv.stats()["pages_in_use"] != 0:
            raise AssertionError("parity prefill leaked KV pages")
    return {"group": [str(d) for d in group], "buckets": buckets,
            "requests": len(results), "tokens_each": sz["new_tokens"],
            "ttft_s": [round(r["ttft_s"], 3) for r in results],
            "prefill_logits_rel_err": float("%.2e" % errs["prefill"]),
            "decode_logits_rel_err": float("%.2e" % errs["decode"]),
            "paged_decode_rel_err": decode_parity,
            "logits_tol": LOGITS_TOL, "donated_kv": pred._donate,
            "sharded": sharded, "memory": memory}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="pre-flight: the same phases at tiny sizes on the "
                         "CPU backend (4 host devices); proves nothing "
                         "about the chip and says so in the summary")
    args = ap.parse_args()

    if args.dry_run_cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4")
    import jax
    import jaxlib

    if args.dry_run_cpu:
        jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, HERE)
    from mxnet_tpu import _native, profiler
    from mxnet_tpu.context import compile_cache_dir

    cache_dir = compile_cache_dir()
    devices = jax.devices()
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu_version}
    print("platform=%s device_kind=%r count=%d jax=%s jaxlib=%s libtpu=%s "
          "compile_cache=%s" % (dev["platform"], dev["kind"], dev["count"],
                                versions["jax"], versions["jaxlib"],
                                versions["libtpu"], cache_dir), flush=True)
    on_tpu = dev["platform"] == "tpu"
    if not on_tpu and not args.dry_run_cpu:
        print("chip_smoke: no TPU — jax.devices()[0].platform is %r "
              "(JAX_PLATFORMS=%r, jax_platforms=%r); nothing was built. "
              "Run on the chip, or pass --dry-run-cpu for a CPU pre-flight."
              % (dev["platform"], os.environ.get("JAX_PLATFORMS"),
                 jax.config.jax_platforms), file=sys.stderr)
        return 2

    sz = TINY if args.dry_run_cpu else FULL
    if len(devices) > 1 and len(devices) % 2:
        raise SystemExit("chip_smoke: %d devices — the multi-chip layout "
                         "needs an even count" % len(devices))
    summary = {"ok": False, "device": dev, "platform": dev["platform"],
               "dry_run": bool(args.dry_run_cpu), "versions": versions,
               "compile_cache": cache_dir,
               "native_runtime": _native.get_lib() is not None,
               "phases": {}}
    meter = CompileMeter()
    t0 = time.perf_counter()
    run_phase("trainer", lambda: phase_trainer(sz, devices), meter, summary)
    gc.collect()
    run_phase("transformer",
              lambda: phase_transformer(sz, devices, on_tpu), meter, summary)
    gc.collect()
    run_phase("server", lambda: phase_server(sz, devices), meter, summary)
    summary["wall_s"] = round(time.perf_counter() - t0, 2)

    # no schedule table is committed, so a hit means state from outside
    # git leaked in (~/.cache/mxnet_tpu/schedule_table.json)
    tuning = profiler.tuning_stats()
    summary["schedule_table_hits"] = int(tuning.get("hits", 0))
    if summary["schedule_table_hits"]:
        finish(summary, ok=False)
        raise SystemExit(
            "chip_smoke: the schedule table answered %d consult(s); no "
            "table is committed, so state leaked in from outside the "
            "checkout: %r" % (summary["schedule_table_hits"], tuning))
    finish(summary, ok=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
