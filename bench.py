#!/usr/bin/env python
"""Headline benchmark: ResNet-50 ImageNet training throughput (img/s).

Mirrors the reference's benchmark mode (example/image-classification
train_imagenet.py with synthetic data; baseline 109 img/s on 1x K80,
example/image-classification/README.md:147-156). Runs the fused SPMD
training step — forward + backward + SGD-momentum update in ONE XLA
program, bf16 compute / fp32 master weights — on all available devices.
Two graph variants:

- ``fused``: the Pallas fused-bottleneck ResNet (kernels/fused_block.py)
- ``unfused``: the plain XLA graph

The parent process never touches JAX: a chip belongs to one process at a
time, so each variant is measured in a fresh subprocess that is the only
one holding the chip (the ``tune`` and ``kernels`` variants are the tools
themselves, started directly by the parent). Every record names the
device it ran on (``platform``/``device_kind``/``device_count``). A
variant that fails, fails the run: the parent prints what landed, lists
the failures and exits non-zero. Nothing is retried, shrunk or cached.

Prints a best-so-far result JSON line after every successful
measurement:
  {"metric": ..., "value": N, "unit": "img/s", "vs_baseline": N, ...}
"""
import json
import os
import subprocess
import sys
import time

BASELINE_IMG_S = 109.0  # ResNet-50, 1x K80, batch 32 (BASELINE.md)
CHILD_TOTAL_TIMEOUT = int(os.environ.get("BENCH_CHILD_TIMEOUT", 1200))
PARENT_BUDGET = int(os.environ.get("BENCH_BUDGET", 2400))
HERE = os.path.dirname(os.path.abspath(__file__))

# per-chip batch of the device-resident train variants. unfused/zero: 512
# and fused: 256 are the sizes of the round-5 record (deleted in PR 21,
# older than PRs 1-20); a size that does not fit is an error, not a
# reason to measure a smaller one under the same name.
TRAIN_BATCH = {"unfused": 512, "zero": 512, "fused": 256}
FIT_BATCH = 128


def _emit(rec):
    """Worker result line: the record plus the device it ran on."""
    from mxnet_tpu.context import device_record

    print(json.dumps(dict(rec, **device_record())))


def _measure(variant):
    """Child: measure one variant, print one JSON record line. Any
    failure propagates: the traceback goes to stderr and the exit code
    is non-zero."""
    sys.path.insert(0, HERE)
    simple = {"serve": _measure_serve, "fleet": _measure_fleet,
              "generate": _measure_generate, "quant": _measure_quant,
              "embed": _measure_embed, "data": _measure_data,
              "autoscale": _measure_autoscale, "fit": _measure_fit,
              "mp": _measure_mp}
    if variant in simple:
        return simple[variant]()
    if variant not in TRAIN_BATCH:
        raise SystemExit("bench.py: unknown variant %r" % variant)

    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.spmd import (TrainStep, data_sharding,
                                         functional_optimizer)

    n_dev = len(jax.devices())
    sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape=(3, 224, 224),
                            fused=(variant == "fused"))
    # zero (ISSUE 7): the unfused graph with the weight-update sharded
    # (reduce-scatter → 1/N update → all-gather); acceptance is per-step
    # time within ~5% of unfused at 1/N per-device optimizer state.
    per_dev_batch = TRAIN_BATCH[variant]
    batch = per_dev_batch * n_dev
    ts = TrainStep(
        sym,
        functional_optimizer("sgd", learning_rate=0.1, momentum=0.9,
                             wd=1e-4),
        mesh=make_mesh({"dp": n_dev}),
        compute_dtype="bfloat16",
        zero=(variant == "zero"),
    )
    params, opt_state, aux = ts.init_params(
        {"data": (batch, 3, 224, 224), "softmax_label": (batch,)},
        initializer=mx.initializer.Xavier(),
    )
    carry = ts.place(params, opt_state, aux)
    rng = np.random.RandomState(0)
    batch_np = {
        "data": rng.randn(batch, 3, 224, 224).astype(np.float32),
        "softmax_label": rng.randint(0, 1000, (batch,)).astype(np.float32),
    }
    key = jax.random.PRNGKey(0)
    sharding = data_sharding(ts.mesh)
    batch_dev = {k: jax.device_put(v, sharding) for k, v in batch_np.items()}

    carry, loss = ts(carry, batch_dev, key)  # compile + warmup
    jax.block_until_ready(loss)
    carry, loss = ts(carry, batch_dev, key)
    jax.block_until_ready(loss)

    n_steps = 20
    t0 = time.perf_counter()
    for _ in range(n_steps):
        carry, loss = ts(carry, batch_dev, key)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    rec = {"img_s": round(batch * n_steps / dt, 2), "variant": variant,
           "batch": per_dev_batch, "loss": float(loss)}
    # compiled-program peak bytes (ISSUE 19): the jitted step is already
    # compiled, so lower().compile() is a cache hit
    mem = ts.compiled_memory_stats(carry, batch_dev, key)
    rec["peak_bytes"] = mem["peak_bytes"]
    rec["temp_bytes"] = mem["temp_bytes"]
    if variant == "zero":
        # measured per-device optimizer-state bytes next to the analytic
        # replicated baseline (momentum = one fp32 copy of every param,
        # replicated on each device)
        mem = ts.memory_stats(carry)
        repl = sum(int(np.prod(tuple(v.shape) or (1,))) * 4
                   for v in carry[0].values())
        rec["opt_bytes_per_dev"] = mem["opt_bytes_per_dev"]
        rec["repl_opt_bytes_per_dev"] = repl
        rec["opt_bytes_ratio"] = round(
            mem["opt_bytes_per_dev"] / max(repl, 1), 4)
    _emit(rec)


def _write_fit_shards(root, n):
    """Synthetic labeled uint8 image records on disk (ISSUE 17): the
    fit variant now reads real record shards through the sharded data
    service instead of in-memory NDArrayIter arrays."""
    import struct

    import numpy as np

    from mxnet_tpu.data import write_record_shards

    rng = np.random.RandomState(0)
    px = 3 * 224 * 224
    records = [
        struct.pack("<f", float(rng.randint(0, 1000)))
        + rng.randint(0, 256, px, dtype=np.uint8).tobytes()
        for _ in range(n)
    ]
    return write_record_shards(root, "fitimgs", records)


def _measure_fit():
    """End-to-end variant (ISSUE 5 + 17): host-fed Module.fit() reading
    on-disk record shards through the sharded data service
    (ShardedRecordStream -> ShardedBatchIter -> DeviceQueueIter) with
    background decode + prefetch, device-resident metrics. Unlike the
    device-resident variants this number includes every per-batch host
    cost of the real training loop — input regressions (feed OR data
    plane) are visible in the trajectory."""
    import shutil
    import tempfile
    from functools import partial

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.data.lease import LocalLeaseAuthority
    from mxnet_tpu.data.service import (ShardedBatchIter,
                                        ShardedRecordStream,
                                        decode_image_f32)
    from mxnet_tpu.models import resnet
    from mxnet_tpu.parallel.feed import DeviceQueueIter

    n_dev = len(jax.devices())
    sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape=(3, 224, 224), fused=False)
    batch = FIT_BATCH * n_dev
    n = batch * 6  # 6 batches/epoch keeps host/disk cost bounded
    root = tempfile.mkdtemp(prefix="bench-fit-")
    stream = None
    try:
        mpath = _write_fit_shards(root, n)
        mod = mx.mod.Module(sym,
                            context=[mx.tpu(i) for i in range(n_dev)])
        times = []
        profiler.pipeline_reset()
        profiler.io_reset()
        stream = ShardedRecordStream(
            mpath, lease_client=LocalLeaseAuthority(ttl=600.0), rank=0,
            decode=partial(decode_image_f32, shape=(3, 224, 224)),
            workers=2, prefetch=4, chunk=batch)
        data_iter = ShardedBatchIter(stream, batch, (3, 224, 224))
        with DeviceQueueIter(data_iter, module=mod) as feed:
            mod.fit(feed, num_epoch=4, kvstore="tpu", optimizer="sgd",
                    optimizer_params={"learning_rate": 0.1,
                                      "momentum": 0.9},
                    initializer=mx.initializer.Xavier(),
                    epoch_end_callback=lambda *_: times.append(
                        time.perf_counter()))
        if mod._fused is None:
            raise RuntimeError("fit: fused path not engaged")
        # epoch 0 pays compile; average the remaining epochs
        img_s = n * (len(times) - 1) / (times[-1] - times[0])
        stats = profiler.pipeline_stats()
        io = profiler.io_stats()
        _emit({"img_s": round(img_s, 2), "variant": "fit",
               "batch": FIT_BATCH,
               "host_syncs": stats.get("host_syncs", 0),
               "avg_put_ms": stats.get("avg_put_ms"),
               "avg_stall_feed_ms": stats.get("avg_stall_feed_ms"),
               "io_records": io.get("records", 0),
               "io_wait_s": round(io.get("wait_seconds", 0.0), 3),
               "io_wait_p99_ms": io.get("input_wait_p99_ms")})
    finally:
        if stream is not None:
            stream.close()
        shutil.rmtree(root, ignore_errors=True)


def _measure_serve():
    """Serving-tier variant (ISSUE 6): dynamic-batching ModelServer
    under closed-loop Poisson load vs batch-1 sequential serving, with
    a checkpoint hot-swap mid-run (tools/bench_serve.py). Tracks req/s,
    tail latency, and the zero-drop swap so serving regressions are
    visible in the trajectory alongside training throughput."""
    from tools.bench_serve import measure

    rec = measure(clients=24, seconds=4.0)
    _emit({
        "variant": "serve",
        "req_s": rec["dynamic"]["req_s"],
        "speedup_vs_sequential": rec["speedup"],
        "p99_ms": rec["dynamic"]["p99_ms"],
        "seq_p99_ms": rec["sequential"]["p99_ms"],
        "batch_fill": rec["dynamic"]["batch_fill"],
        "swap_dropped": rec["dynamic"].get("swap", {}).get("dropped"),
        "swap_errors": rec["dynamic"].get("swap", {}).get("errors"),
    })


def _measure_fleet():
    """Serving-fleet variant (ISSUE 11): 1 router / 3 replica
    PROCESSES discovered through the tracker, closed-loop load with a
    mid-run replica SIGKILL (tools/bench_serve.py --fleet). Tracks
    req/s scaling 1→3, p99, and the shed/retried/failed split — the
    acceptance number is failed == 0 across the kill. Scaling is only
    meaningful with >= 4 cores; the record carries the core count. The
    replicas run on the CPU backend whatever this process holds
    (``replica_platform``)."""
    from tools.bench_serve import measure_fleet

    rec = measure_fleet(replicas=3, clients=16, seconds=4.0)
    _emit({
        "variant": "fleet",
        "req_s": rec["fleet"]["req_s"],
        "single_req_s": rec["single"]["req_s"],
        "scaling": rec["scaling"],
        "p99_ms": rec["fleet"]["p99_ms"],
        "failed": rec["fleet"]["failed"],
        "retried": rec["fleet"]["retried"],
        "failovers": rec["fleet"]["failovers"],
        "inflight_lost": rec["fleet"]["inflight_lost"],
        "shed": rec["fleet"]["shed"],
        "cores": rec["cores"],
        "cores_pinned": rec["cores_pinned"],
        "replica_platform": rec["replica_platform"],
    })


def _measure_autoscale():
    """Elastic-fleet variant (ISSUE 18): stepped load low→high→low
    against an autoscaled fleet vs the static 1-replica baseline
    (tools/bench_serve.py --autoscale), plus the two-tenant QoS trace.
    The record carries the high-phase p99 for both fleets, the replica
    trajectory (peak/final), zero-failed-request evidence across the
    scale events, and the bulk tenant's quota caps. CPU-honest: on a
    small host the elastic replicas contend for the same cores and the
    p99 gap narrows — the core count rides the record, as does the
    platform the replicas ran on (``replica_platform``)."""
    from tools.bench_serve import measure_autoscale

    rec = measure_autoscale(seconds=4.0)
    high = rec["elastic"]["phases"][1]
    two = rec["two_tenant"]
    _emit({
        "variant": "autoscale",
        "req_s": round(high["requests"] / 4.0, 1),
        "p99_ms": rec["value"],
        "static_p99_ms": rec["static_high_p99_ms"],
        "p99_ratio_vs_static": rec["p99_ratio_vs_static"],
        "replicas_peak": rec["elastic"]["replicas_peak"],
        "replicas_final": rec["elastic"]["replicas_final"],
        "failed": rec["elastic"]["failed"] + rec["static"]["failed"],
        "scale_ups": rec["elastic"]["autoscale"]["scale_ups"],
        "retires": rec["elastic"]["autoscale"]["retires"],
        "latency_p99_alone_ms": two["latency_alone"]["p99_ms"],
        "latency_p99_with_bulk_ms": two["together"]["latency_p99_ms"],
        "bulk_admitted": two["bulk_admitted"],
        "bulk_quota_rejections": two["bulk_quota_rejections"],
        "cores": rec["cores"],
        "replica_platform": rec["replica_platform"],
    })


def _measure_generate():
    """Generative-serving variant (ISSUE 12): autoregressive decode
    under Poisson arrivals with sampled prompt/output lengths
    (tools/bench_serve.py --generate) — continuous batching vs
    drain-whole-batch tokens/s, p99 time-to-first-token, and slot
    occupancy. The acceptance pair is speedup >= 2x at equal-or-better
    p99 TTFT; pages_in_use_after == 0 is the paged-allocator
    exactness evidence riding every record.

    The record also carries the ISSUE 16 pair: prefix_speedup (p99
    TTFT, sharing off / on, from --prefix-share — acceptance >= 3x at
    exact prefill-token accounting, zero leaks, identical outputs) and
    spec_tokens_s / spec_speedup / acceptance_rate (from --spec —
    acceptance >= 1.5x tokens/s at byte-identical greedy outputs), so
    the trajectory tracks both levers."""
    from tools.bench_serve import (measure_generate, measure_prefix,
                                   measure_spec)

    rec = measure_generate()
    px = measure_prefix()
    sp = measure_spec()
    _emit({
        "variant": "generate",
        "tokens_s": rec["continuous"]["tokens_s"],
        "speedup_vs_drain": rec["speedup_vs_drain"],
        "ttft_p99_ms": rec["continuous"]["ttft_p99_ms"],
        "drain_tokens_s": rec["drain"]["tokens_s"],
        "drain_ttft_p99_ms": rec["drain"]["ttft_p99_ms"],
        "slot_occupancy": rec["continuous"]["slot_occupancy"],
        "drain_occupancy": rec["drain"]["slot_occupancy"],
        "pages_high_water": rec["continuous"]["pages_high_water"],
        "pages_in_use_after": rec["continuous"]["pages_in_use_after"],
        "prefix_speedup": px["prefix_speedup"],
        "prefix_ttft_p99_ms": px["sharing_on"]["ttft_p99_ms"],
        "prefix_outputs_equal": px["outputs_equal"],
        "prefix_accounting_exact": px["prefill_token_accounting_exact"],
        "prefix_page_leaks": px["sharing_on"]["page_leaks"],
        "spec_tokens_s": sp["spec"]["tokens_s"],
        "spec_speedup": sp["spec_speedup"],
        "acceptance_rate": sp["acceptance_rate"],
        "spec_outputs_equal": sp["outputs_equal"],
    })


def _measure_mp():
    """Tensor-parallel variant (ISSUE 20): the megatron-sharded
    transformer step on the (dp, mp=2) mesh vs the replicated step
    (tools/bench_e2e.measure_mp) — tokens/s, per-chip argument bytes
    (acceptance ~1/mp of the replicated bytes), and the structural
    collective counts (exactly 2 psums per block). Needs an even device
    count; on one chip it fails like any other variant that cannot
    run."""
    from tools.bench_e2e import measure_mp

    rec = measure_mp(mp=2)
    rec["variant"] = "mp"
    _emit(rec)


def _measure_quant():
    """Quantized-serving variant (ISSUE 13): int8 post-training-
    quantized serving vs bf16 on the same closed-loop Poisson trace
    (tools/bench_serve.py --quant int8). The trajectory tracks int8
    req/s, the speedup over bf16, both p99s, and the fixed-corpus
    top-1 agreement — the acceptance pair is speedup > 1 at
    equal-or-better p99 with agreement >= 99%."""
    from tools.bench_serve import measure_quant

    rec = measure_quant(seconds=4.0)
    _emit({
        "variant": "quant",
        "req_s": rec["int8"]["req_s"],
        "speedup_vs_bf16": rec["speedup_vs_bf16"],
        "p99_ms": rec["int8"]["p99_ms"],
        "bf16_p99_ms": rec["bf16"]["p99_ms"],
        "bf16_req_s": rec["bf16"]["req_s"],
        "agreement_top1": rec["agreement_top1"],
        "quantized_ops": rec["quantized_ops"],
        "calib_batches": rec["calib_batches"],
    })


def _measure_embed():
    """Sharded-embedding variant (ISSUE 14): training-shaped rounds
    (dedup zipfian pull + gradient scatter push) against 4 in-process
    row-sharded servers (tools/bench_embed.py). The trajectory tracks
    rows/s, the dedup-vs-naive pull speedup (acceptance >= 2x), the
    async-vs-sync ratio (honest with the core count), and the
    per-server memory ratio (~1/num_servers via memoryStats)."""
    from tools.bench_embed import measure

    rec = measure()
    _emit({
        "variant": "embed",
        "rows_s": rec["train_rows_s"],
        "pull_rows_s": rec["pull_rows_s"],
        "naive_pull_rows_s": rec["naive_pull_rows_s"],
        "speedup_dedup_vs_naive": rec["speedup_dedup_vs_naive"],
        "sync_rows_s": rec["sync_train_rows_s"],
        "async_vs_sync": rec["async_vs_sync"],
        "rows_s_2bit": rec["train_rows_s_2bit"],
        "mem_ratio_max": rec["mem_ratio_max"],
        "servers": rec["servers"],
        "table_mb": rec["table_mb"],
        "dedup_ratio": rec["dedup_ratio"],
        "cores": rec["cores"],
    })


def _measure_data(records=2048):
    """Sharded-data-service variant (ISSUE 17): sync vs prefetched
    input-wait fraction and records/s through ShardedBatchIter over
    on-disk record shards (tools/bench_data.py), with the
    deterministic-replay check asserted in the same run — byte-equal
    decode across a mid-epoch lease handoff. Tracks the input pipeline
    itself so host-side data regressions show in the trajectory."""
    from tools.bench_data import measure

    rec = measure(records=records)
    rec["variant"] = "data"
    _emit(rec)


def _last_json(stdout, keys):
    """The last stdout line that parses as a JSON object holding any of
    ``keys``."""
    for ln in reversed((stdout or "").splitlines()):
        ln = ln.strip()
        if not ln.startswith("{"):
            continue
        try:
            parsed = json.loads(ln)
        except ValueError:
            continue  # stray brace-looking log line
        if any(k in parsed for k in keys):
            return parsed
    return None


def _tune_record(rec):
    """Schedule-autotuner variant (ISSUE 10 + 15): the report of
    tools/tune_kernels.py --compare (exhaustive first, cost-model refit,
    then the ranked sweep) reduced to winner-vs-default AND
    ranked-vs-exhaustive (timed/skipped counts, wall-times, winner delta)
    per kernel — so the trajectory tracks ranked-sweep wall-time next to
    winner quality."""
    tuned = {}
    ranked_wall = exh_wall = 0.0
    for key, r in rec["tune"].items():
        w = r.get("winner") or {}
        exh = r.get("exhaustive") or {}
        recommitted = r.get("recommitted_exhaustive_winner", False)
        tuned[key] = {
            "cache_hit": r.get("cache_hit", False),
            "schedule": w.get("schedule"),
            "ms_per_iter": w.get("ms_per_iter"),
            "default_ms_per_iter": w.get("default_ms_per_iter"),
            "speedup_vs_default": w.get("speedup_vs_default"),
            "n_timed": r.get("n_timed"),
            "n_pruned": r.get("n_pruned"),
            "n_skipped_ranked": r.get("n_skipped_ranked"),
            "ranker": (r.get("ranker") or {}).get("mode"),
            "wall_s": r.get("wall_s"),
            "exhaustive_n_timed": exh.get("n_timed"),
            "exhaustive_wall_s": exh.get("wall_s"),
            "winner_delta_pct": r.get("winner_delta_pct"),
            # what the table actually serves after the run: the compare
            # flow re-commits the exhaustive winner when the ranked one
            # measured slower
            "recommitted_exhaustive_winner": recommitted,
            "committed_schedule": (exh.get("winner_schedule")
                                   if recommitted else w.get("schedule")),
        }
        ranked_wall += r.get("wall_s") or 0.0
        exh_wall += exh.get("wall_s") or 0.0
    out = {"variant": "tune", "tuned": tuned, "table": rec.get("table"),
           **rec["device"]}
    if ranked_wall and exh_wall:
        out["ranked_wall_s"] = round(ranked_wall, 2)
        out["exhaustive_wall_s"] = round(exh_wall, 2)
        out["sweep_speedup"] = round(exh_wall / ranked_wall, 2)
    return out


def _kernels_record(rec):
    """Loop-amortized per-kernel numbers (tools/bench_kernel.py): the
    MXU-utilization evidence behind the fused variant's number, with the
    pallas/xla ratios and the spread verdict."""
    return {"variant": "kernels", "per_kernel": rec["bench_kernel"],
            "ratios": rec.get("ratios"),
            "worst_spread_pct": rec.get("worst_spread_pct"),
            **rec["device"]}


# variants whose child IS a tool (which owns the chip itself): command
# tail, the key its report line carries, the reduction to a record, and
# the exit codes that mean "ran" (bench_kernel: 4 = spread above 10%)
_TOOL_VARIANTS = {
    "tune": (["tools/tune_kernels.py", "--compare"], "tune",
             _tune_record, (0,)),
    "kernels": (["tools/bench_kernel.py"], "bench_kernel",
                _kernels_record, (0, 4)),
}
_WORKER_VARIANTS = ("unfused", "fused", "fit", "zero", "serve", "fleet",
                    "generate", "quant", "embed", "data", "autoscale", "mp")
_RESULT_KEYS = ("img_s", "req_s", "rows_s", "records_s", "tokens_s")


def _report(results, failed):
    imgs = {k: v for k, v in results.items() if "img_s" in v}
    rec = {"metric": "resnet50_imagenet_train_throughput",
           "value": 0.0, "unit": "img/s", "vs_baseline": 0.0}
    if imgs:
        best = max(imgs.values(), key=lambda r: r["img_s"])
        rec.update(value=best["img_s"],
                   vs_baseline=round(best["img_s"] / BASELINE_IMG_S, 3),
                   variant=best["variant"],
                   all={k: v["img_s"] for k, v in imgs.items()})
        rec.update({k: best[k] for k in
                    ("platform", "device_kind", "device_count")})
    for name in ("serve", "fleet", "generate", "quant", "embed", "tune",
                 "kernels", "data", "autoscale", "mp"):
        if name in results:
            rec[name] = {k: v for k, v in results[name].items()
                         if k not in ("variant", "metric", "value", "unit")}
    if "zero" in results:
        rec["zero_mem"] = {
            k: results["zero"][k]
            for k in ("opt_bytes_per_dev", "repl_opt_bytes_per_dev",
                      "opt_bytes_ratio")}
    if failed:
        rec["failed"] = failed
    print(json.dumps(rec))
    sys.stdout.flush()


def _run_variant(variant, timeout):
    """One child process → (record, None) or (None, why it failed)."""
    if variant in _TOOL_VARIANTS:
        tail, key, reduce, ok_codes = _TOOL_VARIANTS[variant]
        keys = (key,)
        cmd = [sys.executable, os.path.join(HERE, tail[0])] + tail[1:]
    else:
        keys, reduce, ok_codes = _RESULT_KEYS, None, (0,)
        cmd = [sys.executable, os.path.abspath(__file__), "--worker",
               variant]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired:
        return None, "child timeout after %ds" % timeout
    why = "rc=%s %s" % (proc.returncode, (proc.stderr or "").strip()[-300:])
    if proc.returncode not in ok_codes:
        return None, why
    parsed = _last_json(proc.stdout, keys)
    if parsed is None:
        return None, "no result " + why
    return (reduce(parsed) if reduce else parsed), None


def main():
    deadline = time.time() + PARENT_BUDGET
    results = {}
    failed = {}
    # unfused first (the known-compiling banker), then the fused
    # headline, then the end-to-end fit loop (ISSUE 5 — host-fed
    # Module.fit through the async input pipeline). A best-so-far line
    # prints after EVERY success, so a run killed mid-way still shows
    # what landed.
    for variant in _WORKER_VARIANTS + tuple(_TOOL_VARIANTS):
        remaining = deadline - time.time()
        if remaining < 60:
            failed[variant] = "not run: BENCH_BUDGET=%ds spent" % PARENT_BUDGET
            continue
        rec, why = _run_variant(variant, int(min(CHILD_TOTAL_TIMEOUT,
                                                  remaining)))
        if rec is None:
            failed[variant] = why
        else:
            results[variant] = rec
            _report(results, failed)
    _report(results, failed)
    if failed:
        for variant, why in failed.items():
            print("bench.py: %s failed: %s" % (variant, why),
                  file=sys.stderr)
        raise SystemExit(3)


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--worker":
        _measure(sys.argv[2])
    else:
        main()
