#!/usr/bin/env python
"""Collective-bandwidth microbenchmark over the device mesh.

Reference counterpart: ``tools/bandwidth/measure.py`` (kvstore push/pull
bandwidth across GPUs/machines). TPU-native: times the XLA collectives
the framework's gradient sync actually compiles to — psum (allreduce),
all_gather, reduce_scatter, ppermute (the ring-attention primitive) —
over the active mesh, and reports algorithmic bandwidth per collective.

On the CPU test mesh the numbers are memcpy-bound but exercise the same
programs; on a real slice they measure ICI.

``--wire`` (ISSUE 4) instead benchmarks the ServerKVStore data plane
against a local in-process KVStoreServer: the push/pull phase wall time
for the synchronous vs async pipelined client and raw vs 2-bit wire
bytes, emitted as ONE bench.py-compatible JSON metric line.
"""
import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def wire_main(args):
    """ServerKVStore push/pull microbenchmark (sync vs pipelined,
    raw vs 2-bit compressed), 1 local server + N worker clients."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from mxnet_tpu import profiler
    from mxnet_tpu.kvstore_server import KVStoreServer, ServerKVStore

    nkeys = args.keys
    elems = max(1, int(args.size_mb * (1 << 20) / 4 / nkeys))
    keys = ["p%03d" % i for i in range(nkeys)]
    grads = [(i % 7 - 3) / 3.0 * (1.0 + (i % 5))
             for i in range(nkeys)]  # deterministic, mixed signs

    import numpy as np

    def phase(pipeline, compress):
        srv = KVStoreServer(num_workers=args.workers)
        srv.serve_in_background()
        clients = [ServerKVStore(srv.addr, pipeline=pipeline)
                   for _ in range(args.workers)]
        if compress:
            for kv in clients:
                kv.set_gradient_compression(
                    {"type": "2bit", "threshold": 0.5})
        for i, k in enumerate(keys):
            clients[0].init(k, np.zeros((elems,), np.float32))
        bufs = [np.full((elems,), g, np.float32) for g in grads]
        profiler.comm_reset()

        errors = []

        def worker(kv):
            # the training loop's shape (model._update_params_on_kvstore):
            # push every key with priority -index, then ONE batched pull
            # — both clients get the batched pull; the sync/async delta
            # isolates the push pipeline. Each worker owns its output
            # buffers, like real workers do.
            try:
                out = [np.empty((elems,), np.float32) for _ in keys]
                for _ in range(args.iters):
                    for i, k in enumerate(keys):
                        kv.push(k, bufs[i], priority=-i)
                    kv.pull(keys, out)
            except BaseException as e:
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(kv,))
                   for kv in clients]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        if errors:
            # a failed phase must fail the benchmark, not emit a metric
            # line computed over work that never moved the payload
            raise errors[0]
        stats = profiler.comm_stats(reset=True)
        for kv in clients:
            kv.close()
        srv.shutdown()
        push = stats.get("push", {})
        return {"seconds": round(dt, 4),
                "raw_bytes": push.get("raw_bytes", 0),
                "wire_bytes": push.get("wire_bytes", 0),
                "rpc_frames": push.get("count", 0),
                "max_inflight": push.get("max_inflight", 0)}

    sync_raw = phase(pipeline=False, compress=False)
    async_raw = phase(pipeline=True, compress=False)
    sync_2bit = phase(pipeline=False, compress=True)
    async_2bit = phase(pipeline=True, compress=True)

    moved_mb = (args.workers * args.iters * nkeys * elems * 4
                / float(1 << 20))
    rec = {
        "metric": "kvstore_wire_push_pull",
        "value": round(moved_mb / async_raw["seconds"], 2),
        "unit": "MB/s",
        "payload_mb": round(moved_mb, 1),
        "workers": args.workers, "keys": nkeys, "iters": args.iters,
        "sync_s": sync_raw["seconds"], "async_s": async_raw["seconds"],
        "async_speedup": round(sync_raw["seconds"]
                               / async_raw["seconds"], 2),
        "sync_2bit_s": sync_2bit["seconds"],
        "async_2bit_s": async_2bit["seconds"],
        "wire_reduction_2bit": round(
            async_2bit["raw_bytes"] / max(async_2bit["wire_bytes"], 1), 2),
        "raw_bytes": async_2bit["raw_bytes"],
        "wire_bytes_2bit": async_2bit["wire_bytes"],
        "wire_bytes_raw": async_raw["wire_bytes"],
        "rpc_frames_async": async_raw["rpc_frames"],
        "rpc_frames_sync": sync_raw["rpc_frames"],
        "max_inflight": async_raw["max_inflight"],
    }
    print(json.dumps(rec))
    sys.stdout.flush()
    # skip interpreter/XLA teardown: the jitted quantize leaves XLA CPU
    # thread pools whose destructor intermittently aborts ("terminate
    # called without an active exception") AFTER the result is printed
    # — the same known teardown crash tests/test_io_pipeline.py already
    # carves out for the other bench tools
    os._exit(0)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--size-mb", type=float, default=None,
                   help="payload MiB (fp32): per device (collectives) "
                        "or total across --keys (--wire). Defaults: 16 "
                        "collectives / 2 wire (training-like key sizes)")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--devices", type=int, default=0,
                   help="0 = all visible devices")
    p.add_argument("--wire", action="store_true",
                   help="benchmark the ServerKVStore data plane "
                        "(sync vs async client, raw vs 2-bit) instead "
                        "of the mesh collectives")
    p.add_argument("--workers", type=int, default=2,
                   help="--wire: concurrent worker clients")
    p.add_argument("--keys", type=int, default=32,
                   help="--wire: number of parameter keys")
    args = p.parse_args()

    if args.wire:
        if args.size_mb is None:
            args.size_mb = 2.0
        wire_main(args)
        return
    if args.size_mb is None:
        args.size_mb = 16.0

    if args.devices:
        os.environ.setdefault(
            "XLA_FLAGS",
            "--xla_force_host_platform_device_count=%d" % args.devices)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    n = args.devices or len(devs)
    devs = devs[:n]
    if n < 2:
        print(json.dumps({"error": "need >=2 devices (got %d); set "
                          "--devices with JAX_PLATFORMS=cpu" % n}))
        return
    mesh = Mesh(np.asarray(devs), ("x",))
    elems = int(args.size_mb * (1 << 20) // 4)
    elems -= elems % n
    x = jax.device_put(
        jnp.arange(n * elems, dtype=jnp.float32).reshape(n, elems),
        NamedSharding(mesh, P("x", None)))

    from mxnet_tpu.util import shard_map

    def timed(name, fn, bytes_moved):
        f = jax.jit(fn)
        jax.block_until_ready(f(x))  # compile + warmup
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = f(x)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / args.iters
        print(json.dumps({
            "metric": "collective_%s" % name, "unit": "GB/s",
            "value": round(bytes_moved / dt / 1e9, 2),
            "payload_mb": round(elems * 4 / (1 << 20), 1),
            "devices": n, "ms": round(dt * 1e3, 3)}))

    sm = lambda fn: shard_map(fn, mesh=mesh, in_specs=P("x", None),
                              out_specs=P("x", None))
    smr = lambda fn: shard_map(fn, mesh=mesh, in_specs=P("x", None),
                               out_specs=P(None))
    payload = elems * 4  # per-device bytes

    # allreduce: ring moves 2(n-1)/n of the payload per device
    timed("psum", smr(lambda a: jax.lax.psum(a, "x")),
          2 * (n - 1) / n * payload)
    # all_gather: (n-1)/n per device
    timed("all_gather",
          shard_map(lambda a: jax.lax.all_gather(a, "x", tiled=True),
                    mesh=mesh, in_specs=P("x", None), out_specs=P(None),
                    check_vma=False),
          (n - 1) / n * payload * n)
    # reduce_scatter
    timed("reduce_scatter",
          shard_map(lambda a: jax.lax.psum_scatter(
              a, "x", scatter_dimension=1, tiled=True),
              mesh=mesh, in_specs=P("x", None), out_specs=P("x", None)),
          (n - 1) / n * payload)
    # ppermute ring step (the ring-attention primitive)
    timed("ppermute",
          sm(lambda a: jax.lax.ppermute(
              a, "x", [(i, (i + 1) % n) for i in range(n)])),
          payload)


if __name__ == "__main__":
    main()
