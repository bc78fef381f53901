#!/usr/bin/env python
"""End-to-end training benchmark: RecordIO decode -> infeed -> fused step.

The headline bench (bench.py) times the compute step on synthetic
device-resident batches, exactly like the reference's --benchmark 1
mode. The reference's published numbers are END-TO-END — its
iter_image_recordio_2.cc decode pipeline feeds real training. This
tool closes that gap: it drives ImageRecordIter's threaded fast path
into the SAME fused TrainStep and reports the coupled rate next to the
decode-only and compute-only rates, labelling which side limits.

Prints ONE JSON line:
  {"metric": "resnet_e2e_train_throughput", "value": <coupled img/s>,
   "io_img_s": ..., "synthetic_img_s": ..., "bottleneck": "decode|compute",
   ...config}
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure_mp(mp=2, d_model=256, n_layers=4, seq=64, batch_per_dp=2,
               steps=8):
    """Tensor-parallel measurement (ISSUE 20): the megatron-sharded
    transformer train step on the ``(dp, mp)`` mesh vs the same model
    replicated — step time, per-chip argument bytes from XLA's compiled
    memory analysis, and the structural collective counts (psums per
    block asserted exactly 2). Shared by ``bench.py``'s "mp" variant
    and ``tpu_kernel_smoke.py --mp`` (the scripted on-chip half)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu import profiler
    from mxnet_tpu.context import device_record, kernel_platform
    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.parallel.mesh import train_mesh

    n_dev = len(jax.devices())
    mp = int(mp)
    if mp < 2 or n_dev % mp != 0:
        raise ValueError("measure_mp: mp=%d must be >= 2 and divide the "
                         "%d visible devices" % (mp, n_dev))
    cfg = tfm.TransformerConfig(
        vocab=4096, d_model=d_model, n_heads=8, d_ff=4 * d_model,
        n_layers=n_layers, max_len=seq,
        dtype="bfloat16" if kernel_platform() == "tpu" else "float32")
    params = tfm.init_params(cfg, seed=0)
    rng = np.random.RandomState(0)
    # One global batch (divisible by every dp size) so the mp and
    # dp-only losses are directly comparable.
    tokens = rng.randint(0, cfg.vocab,
                         (batch_per_dp * n_dev, seq + 1)).astype(np.int32)

    def step_time(mesh):
        loss, specs = tfm.make_loss_fn(cfg, mesh)
        pp = {k: jax.device_put(v, NamedSharding(mesh, specs.get(k, P())))
              for k, v in params.items()}
        tt = jax.device_put(jnp.asarray(tokens),
                            NamedSharding(mesh, P("dp")))
        g = jax.jit(jax.value_and_grad(loss))
        compiled = g.lower(pp, tt).compile()
        val, grads = g(pp, tt)      # warm
        jax.block_until_ready(grads)
        t0 = time.perf_counter()
        for _ in range(steps):
            val, grads = g(pp, tt)
        jax.block_until_ready(grads)
        dt = (time.perf_counter() - t0) / steps
        mem = compiled.memory_analysis()
        return {
            "step_ms": round(dt * 1e3, 3),
            "tokens_s": round(tokens.shape[0] * seq / dt, 1),
            "arg_bytes_per_chip": int(mem.argument_size_in_bytes),
            "loss": float(val),
        }

    mesh_mp = train_mesh(mp=mp)
    mesh_dp = train_mesh(mp=1)
    counts = tfm.block_collective_counts(cfg, mesh_mp)
    assert counts["psum_per_block"] == 2, counts  # the megatron contract
    r_mp = step_time(mesh_mp)
    r_dp = step_time(mesh_dp)
    profiler.mp_record(
        mp_size=mp, dp_size=n_dev // mp, group_size=n_dev,
        psum_per_block=counts["psum_per_block"],
        all_gather_per_step=counts["all_gather"],
        collectives_per_step=(counts["psum_per_block"] * cfg.n_layers
                              + counts["psum_outside"]
                              + counts["all_gather"]),
        param_bytes_per_chip=r_mp["arg_bytes_per_chip"])
    return {
        "mp": mp, "dp": n_dev // mp, "devices": n_dev,
        "d_model": d_model, "n_layers": n_layers, "seq": seq,
        "tokens_s": r_mp["tokens_s"],
        "step_ms": r_mp["step_ms"],
        "dp_only_step_ms": r_dp["step_ms"],
        "arg_bytes_per_chip": r_mp["arg_bytes_per_chip"],
        "dp_only_arg_bytes_per_chip": r_dp["arg_bytes_per_chip"],
        "bytes_ratio": round(r_mp["arg_bytes_per_chip"]
                             / max(r_dp["arg_bytes_per_chip"], 1), 4),
        "psum_per_block": counts["psum_per_block"],
        "psum_outside": counts["psum_outside"],
        "all_gather_per_step": counts["all_gather"],
        "loss_abs_diff": round(abs(r_mp["loss"] - r_dp["loss"]), 8),
        **device_record(),
    }


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--num-images", type=int, default=512)
    p.add_argument("--edge", type=int, default=256)
    p.add_argument("--data-shape", type=int, default=224)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--num-layers", type=int, default=50)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--threads", type=int, default=os.cpu_count() or 4)
    p.add_argument("--epochs", type=int, default=2,
                   help="measured epochs over the packed dataset")
    p.add_argument("--fused", action="store_true",
                   help="use the Pallas fused-bottleneck graph")
    p.add_argument("--zero", action="store_true",
                   help="also measure the ZeRO weight-update-sharded "
                        "step (ISSUE 7) and report its img/s and "
                        "measured per-device optimizer-state bytes "
                        "next to the replicated baseline")
    p.add_argument("--sentinel", action="store_true",
                   help="also measure the in-graph anomaly sentinel "
                        "(ISSUE 9, MXNET_TPU_SENTINEL=skip) and report "
                        "its img/s next to the sentinel-off rate — the "
                        "tracked overhead number (acceptance <= 2%%)")
    p.add_argument("--fit-loop", action="store_true",
                   help="also run Module.fit() behind the async input "
                        "pipeline (DeviceQueueIter + device metrics) and "
                        "report host-fed fit img/s next to the "
                        "device-resident rate (ISSUE 5)")
    p.add_argument("--mp", type=int, default=0, metavar="N",
                   help="also measure the megatron tensor-parallel "
                        "transformer step on the (dp, mp=N) mesh "
                        "(ISSUE 20) and report tokens/s, per-chip "
                        "argument bytes vs the replicated step "
                        "(~1/N expected), and the collective counts")
    p.add_argument("--workdir", default="/tmp/mxtpu_bench_e2e")
    args = p.parse_args()

    import jax

    import mxnet_tpu as mx
    from bench_io import pack_dataset
    from mxnet_tpu.context import device_record, kernel_platform
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.spmd import (TrainStep, data_sharding,
                                         functional_optimizer)
    from mxnet_tpu.models import resnet

    # bf16 on the chip, fp32 on the cpu test backend, nothing else
    compute_dtype = "bfloat16" if kernel_platform() == "tpu" else None
    os.makedirs(args.workdir, exist_ok=True)
    prefix = os.path.join(args.workdir, "e2e%d_%d" % (args.num_images,
                                                      args.edge))
    if not os.path.exists(prefix + ".rec"):
        pack_dataset(prefix, args.num_images, args.edge)

    ds = args.data_shape
    sym = resnet.get_symbol(num_classes=args.num_classes,
                            num_layers=args.num_layers,
                            image_shape=(3, ds, ds), fused=args.fused)
    n_dev = len(jax.devices())
    batch = args.batch_size
    ts = TrainStep(
        sym, functional_optimizer("sgd", learning_rate=0.1, momentum=0.9),
        mesh=make_mesh({"dp": n_dev}),
        compute_dtype=compute_dtype,
    )
    params, opt_state, aux = ts.init_params(
        {"data": (batch, 3, ds, ds), "softmax_label": (batch,)},
        initializer=mx.initializer.Xavier())
    carry = ts.place(params, opt_state, aux)
    sharding = data_sharding(ts.mesh)
    key = jax.random.PRNGKey(0)

    def make_iter():
        return mx.io.ImageRecordIter(
            path_imgrec=prefix + ".rec", data_shape=(3, ds, ds),
            batch_size=batch, shuffle=False, rand_crop=True,
            rand_mirror=True, preprocess_threads=args.threads,
            label_name="softmax_label")

    # -- compute-only: synthetic device-resident batch -------------------
    rng = np.random.RandomState(0)
    syn = {"data": jax.device_put(
        rng.randn(batch, 3, ds, ds).astype(np.float32), sharding),
        "softmax_label": jax.device_put(
            rng.randint(0, args.num_classes, (batch,)).astype(np.float32),
            sharding)}
    carry, loss = ts(carry, syn, key)       # compile
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    n_syn = 8
    for _ in range(n_syn):
        carry, loss = ts(carry, syn, key)
    jax.block_until_ready(loss)
    synthetic_img_s = batch * n_syn / (time.perf_counter() - t0)
    repl_mem = ts.memory_stats(carry)
    try:
        # compiled-program bytes (ISSUE 19) — a cache hit, the step is
        # already compiled; best-effort where the backend lacks it
        compiled_mem = ts.compiled_memory_stats(carry, syn, key)
    except Exception:
        compiled_mem = None

    # -- ZeRO variant (ISSUE 7): same graph, weight-update sharded -------
    zero_rec = None
    if args.zero:
        ts_z = TrainStep(
            sym, functional_optimizer("sgd", learning_rate=0.1,
                                      momentum=0.9),
            mesh=make_mesh({"dp": n_dev}), zero=True,
            compute_dtype=compute_dtype,
        )
        p_z, s_z, a_z = ts_z.init_params(
            {"data": (batch, 3, ds, ds), "softmax_label": (batch,)},
            initializer=mx.initializer.Xavier())
        carry_z = ts_z.place(p_z, s_z, a_z)
        carry_z, loss_z = ts_z(carry_z, syn, key)   # compile
        jax.block_until_ready(loss_z)
        t0 = time.perf_counter()
        for _ in range(n_syn):
            carry_z, loss_z = ts_z(carry_z, syn, key)
        jax.block_until_ready(loss_z)
        zero_img_s = batch * n_syn / (time.perf_counter() - t0)
        zero_mem = ts_z.memory_stats(carry_z)
        zero_rec = {
            "img_s": round(zero_img_s, 2),
            "vs_replicated": round(zero_img_s / synthetic_img_s, 3),
            "opt_bytes_per_dev": zero_mem["opt_bytes_per_dev"],
            "repl_opt_bytes_per_dev": repl_mem["opt_bytes_per_dev"],
            "opt_bytes_ratio": round(
                zero_mem["opt_bytes_per_dev"]
                / max(repl_mem["opt_bytes_per_dev"], 1), 4),
            "num_shards": zero_mem["num_shards"],
        }
        del carry_z

    # -- sentinel variant (ISSUE 9): same graph, in-graph health word ----
    sentinel_rec = None
    if args.sentinel:
        ts_s = TrainStep(
            sym, functional_optimizer("sgd", learning_rate=0.1,
                                      momentum=0.9),
            mesh=make_mesh({"dp": n_dev}), sentinel="skip",
            compute_dtype=compute_dtype,
        )
        p_s, s_s, a_s = ts_s.init_params(
            {"data": (batch, 3, ds, ds), "softmax_label": (batch,)},
            initializer=mx.initializer.Xavier())
        carry_s = ts_s.place(p_s, s_s, a_s)
        carry_s, loss_s = ts_s(carry_s, syn, key)   # compile
        jax.block_until_ready(loss_s)
        t0 = time.perf_counter()
        for _ in range(n_syn):
            carry_s, loss_s = ts_s(carry_s, syn, key)
        jax.block_until_ready(loss_s)
        sentinel_img_s = batch * n_syn / (time.perf_counter() - t0)
        health = ts_s.health_stats(carry_s)
        sentinel_rec = {
            "img_s": round(sentinel_img_s, 2),
            "vs_off": round(sentinel_img_s / synthetic_img_s, 4),
            "mode": "skip",
            "healthy_steps": health["healthy"],
            "unhealthy_steps": health["unhealthy"],
        }
        del carry_s

    # -- decode-only ------------------------------------------------------
    it = make_iter()
    n_batches = 0
    t0 = time.perf_counter()
    for b in it:
        n_batches += 1
    io_img_s = batch * n_batches / (time.perf_counter() - t0)

    # -- coupled: iterator feeds the fused step --------------------------
    n_coupled = 0
    t0 = time.perf_counter()
    for _epoch in range(args.epochs):
        it.reset()
        for b in it:
            feed = {"data": jax.device_put(b.data[0].asnumpy(), sharding),
                    "softmax_label": jax.device_put(
                        b.label[0].asnumpy(), sharding)}
            # async dispatch: the next batch decodes while this step runs
            carry, loss = ts(carry, feed, key)
            n_coupled += 1
    jax.block_until_ready(loss)
    coupled_img_s = batch * n_coupled / (time.perf_counter() - t0)

    # -- fit-loop mode: the full Module.fit machinery, host-fed ----------
    fit_img_s = None
    fit_pipe = {}
    if args.fit_loop:
        from mxnet_tpu import profiler
        from mxnet_tpu.parallel.feed import DeviceQueueIter

        contexts = [mx.tpu(i) for i in range(len(jax.devices()))]
        n_fit = batch * max(2, args.num_images // batch)
        rng_f = np.random.RandomState(1)
        Xf = rng_f.randn(n_fit, 3, ds, ds).astype(np.float32)
        yf = rng_f.randint(0, args.num_classes, (n_fit,)).astype(np.float32)
        mod = mx.mod.Module(sym, context=contexts)
        fit_t = []
        profiler.pipeline_reset()  # scope the counters to this fit
        with DeviceQueueIter(mx.io.NDArrayIter(Xf, yf, batch_size=batch),
                             module=mod) as fit_feed:
            mod.fit(fit_feed,
                    num_epoch=args.epochs + 1, kvstore="tpu",
                    optimizer="sgd",
                    optimizer_params={"learning_rate": 0.05,
                                      "momentum": 0.9},
                    initializer=mx.initializer.Xavier(),
                    epoch_end_callback=lambda *_: fit_t.append(
                        (time.perf_counter(), profiler.pipeline_stats())))
        assert mod._fused is not None, "fused path did not engage"
        # epoch 0 pays compile; rate AND counters over the remaining
        # epochs only (cumulative totals would fold warmup syncs into
        # the steady-state stall evidence)
        fit_img_s = n_fit * args.epochs / (fit_t[-1][0] - fit_t[0][0])
        first, last = fit_t[0][1], fit_t[-1][1]
        fit_pipe = {k: last[k] - first[k]
                    for k in ("host_syncs", "preplaced")}

    rec = {
        "metric": "resnet_e2e_train_throughput",
        "value": round(coupled_img_s, 2), "unit": "img/s",
        "io_img_s": round(io_img_s, 2),
        "synthetic_img_s": round(synthetic_img_s, 2),
        "bottleneck": "decode" if io_img_s < synthetic_img_s else "compute",
        "num_layers": args.num_layers, "data_shape": ds,
        "batch_size": batch, "threads": args.threads,
        "fused": bool(args.fused), **device_record(),
    }
    if compiled_mem is not None:
        rec["peak_bytes"] = compiled_mem["peak_bytes"]
        rec["temp_bytes"] = compiled_mem["temp_bytes"]
    if fit_img_s is not None:
        rec["fit_img_s"] = round(fit_img_s, 2)
        rec["fit_host_syncs"] = fit_pipe.get("host_syncs", 0)
        rec["fit_preplaced"] = fit_pipe.get("preplaced", 0)
    if zero_rec is not None:
        rec["zero"] = zero_rec
    if sentinel_rec is not None:
        rec["sentinel"] = sentinel_rec
    if args.mp and args.mp > 1:
        rec["mp"] = measure_mp(mp=args.mp)
    # kvstore data-plane counters (raw vs wire bytes, RPC latency) ride
    # along when this process did distributed push/pull — the ISSUE 4
    # observability surface, empty on the single-chip path
    from mxnet_tpu import profiler

    comm = profiler.comm_stats()
    if comm:
        rec["comm"] = comm
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
