#!/usr/bin/env python
"""Closed-loop Poisson-load serving benchmark (ISSUE 6 acceptance).

N client threads each run a closed loop against a :class:`ModelServer`:
draw an exponential think time, submit one request, block on its
future, repeat. Two serving configurations are measured on the same
model, load, and client count:

- ``sequential``: batch ladder (1,) — every request is its own forward,
  the reference predictor's serving model (the baseline);
- ``dynamic``: the full bucket ladder — concurrent requests coalesce
  into the largest ready bucket.

Mid-run the dynamic measurement hot-swaps the model's weights from a
two-artifact checkpoint (``ModelServer.swap_from_checkpoint``); the
benchmark asserts zero dropped/errored requests across the swap and
reports both configurations' req/s and p50/p99 latency plus the
dynamic batch-fill ratio in ONE bench.py-style JSON line.

Acceptance (ISSUE 6): dynamic >= 2x sequential req/s at equal-or-better
p99, swap completes with dropped == errors == 0.

``--quant int8`` (ISSUE 13) serves the same closed-loop Poisson trace
at bf16 and at int8 (post-training quantized through the IR pass
framework: weights quantized at bind time by the shared fold pass,
activations at the bound boundary) and reports both modes' req/s and
p99 plus int8-vs-bf16 top-1 agreement on a fixed logits corpus —
acceptance is int8 req/s > bf16 at equal-or-better p99 with
agreement >= 99%.

``--fleet`` (ISSUE 11) measures req/s scaling across replica processes;
``--generate`` (ISSUE 12) measures the autoregressive-decode workload:
the same Poisson arrival trace (sampled prompt/output lengths) replayed
under continuous batching and under drain-whole-batch admission,
reporting tokens/s, p99 time-to-first-token, and slot occupancy —
acceptance is continuous >= 2x drain tokens/s at equal-or-better p99
TTFT with every KV page returned.

``--prefix-share`` and ``--spec k`` (ISSUE 16) measure the generative
tier's two sharing/speculation levers on the same replayed-trace
pattern: the radix shared-prefix KV cache (one ~70%-shared-prefix
Poisson trace with sharing off vs on — p99 TTFT, a prefill-token drop
exactly equal to prefill_tokens_saved, zero page leaks, byte-identical
outputs) and speculative decoding (k-token truncated self-draft
proposals verified in one batched target step vs plain decode —
tokens/s and acceptance rate, outputs asserted identical).
"""
import argparse
import json
import os
import random
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_model(dim, hidden, layers, classes, seed=0):
    """An MLP classifier sized so a batched forward amortizes real
    per-call work (dispatch + GEMM), plus random frozen params."""
    import numpy as np

    import mxnet_tpu as mx

    net = mx.sym.var("data")
    for i in range(layers):
        net = mx.sym.Activation(
            mx.sym.FullyConnected(data=net, num_hidden=hidden,
                                  name="fc%d" % i), act_type="relu")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data=net, num_hidden=classes, name="head"),
        name="softmax")
    arg_shapes, _, _ = net.infer_shape(data=(1, dim))
    rng = np.random.RandomState(seed)
    args = {n: (rng.randn(*s) * 0.05).astype(np.float32)
            for n, s in zip(net.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    return net, args


def _client(server, stop_at, think_s, dim, rows, seed, out,
            deadline_s=None):
    """One closed-loop client: think (Exp), submit, wait, record. With
    ``deadline_s`` the request is sheddable (ISSUE 9 overload
    shedding): a DeadlineExceeded is counted as shed — and its
    fail-fast latency recorded separately — not as an error."""
    import numpy as np

    from mxnet_tpu.serving import DeadlineExceeded

    rng = random.Random(seed)
    nrng = np.random.RandomState(seed)
    x = nrng.randn(rows, dim).astype(np.float32)
    lat, shed_lat, errors = [], [], 0
    while time.perf_counter() < stop_at:
        if think_s > 0:
            time.sleep(rng.expovariate(1.0 / think_s))
        t0 = time.perf_counter()
        try:
            server.submit("model", x, deadline=deadline_s).result(timeout=60)
            lat.append(time.perf_counter() - t0)
        except DeadlineExceeded:
            shed_lat.append(time.perf_counter() - t0)
        except Exception:
            errors += 1
    out.append((lat, errors, shed_lat))


def _pctl(sorted_vals, q):
    return sorted_vals[int(round(q * (len(sorted_vals) - 1)))]


def run_mode(symbol, args_np, ladder, clients, seconds, think_ms, dim,
             rows, swap_prefix=None, deadline_ms=None, dtype=None,
             quant=None, calib=None, warm_ladder=False):
    """Measure one serving configuration; returns a result dict.
    ``dtype``/``quant``/``calib`` ride through to the AOTPredictor
    bind (the --quant int8-vs-bf16 comparison); ``warm_ladder``
    compiles EVERY bucket outside the clock so neither quant mode pays
    compiles inside its measured window."""
    import numpy as np

    from mxnet_tpu import profiler
    from mxnet_tpu.serving import ModelServer

    profiler.serving_reset()
    results = []
    deadline_s = None if deadline_ms is None else deadline_ms / 1e3
    pred_kwargs = {}
    if dtype is not None:
        pred_kwargs["dtype"] = dtype
    if quant is not None:
        pred_kwargs["quant"] = quant
        pred_kwargs["calib_data"] = calib
    with ModelServer(ladder=ladder, queue_depth=4 * clients + 8,
                     submit_timeout=60) as server:
        server.add_model("model", symbol=symbol, arg_params=args_np,
                         data_shapes={"data": (1, dim)}, **pred_kwargs)
        warm = sorted({b for b in ladder if b >= rows} or {ladder[-1]}) \
            if warm_ladder else [rows]
        for wrows in warm:  # compile warmup outside the clock
            server.predict("model", np.zeros((wrows, dim), "float32"))
        t0 = time.perf_counter()
        stop_at = t0 + seconds
        threads = [threading.Thread(
            target=_client,
            args=(server, stop_at, think_ms / 1e3, dim, rows, 1000 + i,
                  results, deadline_s))
            for i in range(clients)]
        for t in threads:
            t.start()
        swapped = None
        if swap_prefix is not None:
            # hot-swap mid-load: the acceptance choreography
            time.sleep(seconds / 2.0)
            n = server.swap_from_checkpoint("model", prefix=swap_prefix,
                                            epoch=0)
            swapped = {"params_swapped": n,
                       "at_s": round(time.perf_counter() - t0, 2)}
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
    lats = sorted(x for lat, _e, _s in results for x in lat)
    errors = sum(e for _l, e, _s in results)
    shed_lats = sorted(x for _l, _e, s in results for x in s)
    stats = profiler.serving_stats(reset=True).get("model", {})
    rec = {
        "req_s": round(len(lats) / wall, 1),
        "requests": len(lats),
        "errors": errors,
        "p50_ms": round(_pctl(lats, 0.50) * 1e3, 2) if lats else None,
        "p99_ms": round(_pctl(lats, 0.99) * 1e3, 2) if lats else None,
        "batch_fill": stats.get("batch_fill"),
        "avg_batch_rows": stats.get("avg_batch_rows"),
        "max_queue_depth": stats.get("max_queue_depth"),
    }
    if deadline_ms is not None:
        # shed requests failed FAST (at dequeue) — their p99 is the
        # overload-protection evidence next to the served p99
        rec["deadline_ms"] = deadline_ms
        rec["shed"] = stats.get("shed", 0)
        rec["shed_p99_ms"] = round(_pctl(shed_lats, 0.99) * 1e3, 2) \
            if shed_lats else None
    if swapped is not None:
        # a request neither answered nor errored would still hold a
        # client thread; all joined above, so dropped == 0 by
        # construction — report it as the swap's acceptance number
        swapped["dropped"] = 0
        swapped["errors"] = errors
        rec["swap"] = swapped
    return rec


def measure(clients=32, seconds=6.0, think_ms=1.0, dim=128, hidden=256,
            layers=4, classes=32, rows=1, ladder=None, deadline_ms=25.0):
    """Run both configurations plus the overload-shedding case;
    returns the combined record."""
    import jax
    import numpy as np

    from mxnet_tpu.model import save_checkpoint
    from mxnet_tpu.serving import env_batch_ladder

    ladder = env_batch_ladder() if ladder is None else ladder
    symbol, args_np = build_model(dim, hidden, layers, classes)
    _, args_v2 = build_model(dim, hidden, layers, classes, seed=7)

    # the hot-swap source: a two-artifact checkpoint of the v2 weights
    tmpdir = tempfile.mkdtemp(prefix="bench_serve_")
    prefix = os.path.join(tmpdir, "model")
    save_checkpoint(prefix, 0, symbol,
                    {k: _nd(v) for k, v in args_v2.items()}, {})

    seq = run_mode(symbol, args_np, (1,), clients, seconds, think_ms,
                   dim, rows)
    dyn = run_mode(symbol, args_np, ladder, clients, seconds, think_ms,
                   dim, rows, swap_prefix=prefix)
    # overload: double the clients, zero think time, per-request
    # deadlines — expired requests are shed at dequeue instead of
    # occupying batch slots (ISSUE 9 overload protection)
    over = None
    if deadline_ms and deadline_ms > 0:
        over = run_mode(symbol, args_np, ladder, clients * 2,
                        max(2.0, seconds / 2.0), 0.0, dim, rows,
                        deadline_ms=deadline_ms)
    rec = {
        "metric": "serving_throughput",
        "value": dyn["req_s"],
        "unit": "req/s",
        "speedup": round(dyn["req_s"] / seq["req_s"], 2)
        if seq["req_s"] else None,
        "sequential": seq,
        "dynamic": dyn,
        "ladder": list(ladder),
        "clients": clients,
        "seconds": seconds,
        "think_ms": think_ms,
        "model": {"dim": dim, "hidden": hidden, "layers": layers},
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
    }
    if over is not None:
        rec["overload"] = over
    return rec


def _nd(v):
    from mxnet_tpu import nd

    return nd.array(v)


# ---------------------------------------------------------------------------
# fleet mode (ISSUE 11): N replica PROCESSES behind a FleetRouter,
# discovered through an in-process tracker — req/s scaling 1→R, p99,
# shed/retried/failed counts, with a mid-run replica SIGKILL.
# ---------------------------------------------------------------------------
REPLICA_BOOT_CODE = ("import sys; from mxnet_tpu.serving import fleet; "
                     "sys.exit(fleet.main())")


def _generate_dtype():
    """bf16 on the chip, fp32 on the cpu test backend; any other backend
    raises (context.kernel_platform)."""
    from mxnet_tpu.context import kernel_platform

    return "bfloat16" if kernel_platform() == "tpu" else "float32"


def _replica_env():
    """The replica subprocesses' environment: clean_dist_env, which pins
    JAX to the CPU backend — a chip belongs to one process, and this
    control-plane bench measures routing, not the device."""
    from mxnet_tpu.test_utils import clean_dist_env

    return clean_dist_env(repo_root=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _replica_platform():
    """What fleet/autoscale records name as the platform their replicas
    ran on — read from the environment they are actually given, never
    from the measuring process's own backend."""
    return _replica_env()["JAX_PLATFORMS"]


def _spawn_replica(rank, coord, prefix, dim, ladder, pin_core=None):
    """One replica subprocess (CPU-pinned when asked: on a shared host
    per-replica core pinning is what makes process-level scaling
    measurable at all)."""
    import subprocess

    env = _replica_env()
    host, port = coord.rsplit(":", 1)
    env.update({"DMLC_ROLE": "replica", "DMLC_REPLICA_ID": str(rank),
                "DMLC_PS_ROOT_URI": host, "DMLC_PS_ROOT_PORT": port})
    cmd = [sys.executable, "-c", REPLICA_BOOT_CODE, "replica",
           "--prefix", prefix, "--epoch", "0",
           "--data-shape", "data:1,%d" % dim,
           "--ladder", ",".join(str(b) for b in ladder)]
    if pin_core is not None:
        cmd += ["--pin-core", str(pin_core)]
    return subprocess.Popen(cmd, env=env)


def _fleet_client(router, stop_at, think_s, dim, rows, seed, out):
    """Closed-loop fleet client: think (Exp), route, record. Typed
    overload (FleetOverloaded/shed) is counted separately from genuine
    failures — the acceptance number is failed == 0."""
    import numpy as np

    from mxnet_tpu.serving import FleetOverloaded

    rng = random.Random(seed)
    nrng = np.random.RandomState(seed)
    x = nrng.randn(rows, dim).astype(np.float32)
    lat, overloaded, errors = [], 0, []
    while time.perf_counter() < stop_at:
        if think_s > 0:
            time.sleep(rng.expovariate(1.0 / think_s))
        t0 = time.perf_counter()
        try:
            router.request("model", x, timeout=20.0)
            lat.append(time.perf_counter() - t0)
        except FleetOverloaded:
            overloaded += 1
        except Exception as e:
            errors.append("%s: %s" % (type(e).__name__, e))
    out.append((lat, overloaded, errors))


def run_fleet_mode(prefix, dim, num_replicas, clients, seconds, think_ms,
                   rows=1, ladder=(1, 4, 16), kill_mid_run=False,
                   pin_cores=False):
    """Measure one fleet size; returns (record, stats)."""
    import signal as _signal

    from mxnet_tpu import profiler
    from mxnet_tpu.serving import FleetRouter
    from mxnet_tpu.tracker import Tracker

    cores = sorted(os.sched_getaffinity(0)) \
        if hasattr(os, "sched_getaffinity") else []
    tracker = Tracker(num_workers=0, num_servers=0)
    tracker.serve_in_background()
    procs = [_spawn_replica(
        r, tracker.addr, prefix, dim, ladder,
        pin_core=cores[r % len(cores)]
        if pin_cores and len(cores) >= num_replicas else None)
        for r in range(num_replicas)]
    profiler.fleet_reset()
    router = FleetRouter(tracker_uri=tracker.addr, view_interval=0.5,
                         timeout=20.0)
    try:
        deadline = time.monotonic() + 120
        while sum(1 for _a, s, alive, _l in router.replicas()
                  if alive and s == "serving") < num_replicas:
            if time.monotonic() > deadline:
                raise RuntimeError("fleet never came up: %s"
                                   % (router.replicas(),))
            time.sleep(0.25)
            router.refresh_view(force=True)
        results = []
        t0 = time.perf_counter()
        stop_at = t0 + seconds
        threads = [threading.Thread(
            target=_fleet_client,
            args=(router, stop_at, think_ms / 1e3, dim, rows, 2000 + i,
                  results)) for i in range(clients)]
        for t in threads:
            t.start()
        killed = None
        if kill_mid_run:
            time.sleep(seconds / 2.0)
            victim = procs[-1]
            victim.send_signal(_signal.SIGKILL)
            killed = {"pid": victim.pid,
                      "at_s": round(time.perf_counter() - t0, 2)}
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        lats = sorted(x for lat, _o, _e in results for x in lat)
        overloaded = sum(o for _l, o, _e in results)
        errors = [e for _l, _o, es in results for e in es]
        stats = profiler.fleet_stats(reset=True)
        rec = {
            "replicas": num_replicas,
            "req_s": round(len(lats) / wall, 1),
            "requests": len(lats),
            "failed": len(errors),
            "failed_examples": errors[:3],
            "overloaded": overloaded,
            "retried": stats.get("retries", 0),
            "failovers": stats.get("failovers", 0),
            "inflight_lost": stats.get("inflight_lost", 0),
            "shed": stats.get("overload_rejections", 0),
            "p50_ms": round(_pctl(lats, 0.50) * 1e3, 2) if lats else None,
            "p99_ms": round(_pctl(lats, 0.99) * 1e3, 2) if lats else None,
        }
        if killed is not None:
            rec["killed"] = killed
        return rec
    finally:
        try:
            router.stop_fleet()
        except Exception:
            pass
        router.close()
        for p in procs:
            try:
                p.wait(timeout=15)
            except Exception:
                p.kill()
        tracker.shutdown()


def measure_fleet(replicas=3, clients=24, seconds=6.0, think_ms=1.0,
                  dim=128, hidden=256, layers=4, classes=32, rows=1):
    """The --fleet record: req/s at 1 replica vs N replicas (each its
    own process, core-pinned when the host has enough cores), with a
    mid-run SIGKILL of one replica during the N-replica window. The
    scaling ratio is only meaningful with >= replicas+1 cores — the
    record carries the core count so the trajectory tooling can tell a
    regression from a small host."""
    from mxnet_tpu.model import save_checkpoint

    symbol, args_np = build_model(dim, hidden, layers, classes)
    tmpdir = tempfile.mkdtemp(prefix="bench_fleet_")
    prefix = os.path.join(tmpdir, "model")
    save_checkpoint(prefix, 0, symbol,
                    {k: _nd(v) for k, v in args_np.items()}, {})
    cores = len(os.sched_getaffinity(0)) \
        if hasattr(os, "sched_getaffinity") else (os.cpu_count() or 1)
    pin = cores >= replicas + 1
    single = run_fleet_mode(prefix, dim, 1, clients, seconds, think_ms,
                            rows=rows, pin_cores=pin)
    fleet = run_fleet_mode(prefix, dim, replicas, clients, seconds,
                           think_ms, rows=rows, kill_mid_run=True,
                           pin_cores=pin)
    rec = {
        "metric": "fleet_serving_throughput",
        "value": fleet["req_s"],
        "unit": "req/s",
        "scaling": round(fleet["req_s"] / single["req_s"], 2)
        if single["req_s"] else None,
        "single": single,
        "fleet": fleet,
        "clients": clients,
        "seconds": seconds,
        "think_ms": think_ms,
        "cores": cores,
        "cores_pinned": pin,
        "model": {"dim": dim, "hidden": hidden, "layers": layers},
        "replica_platform": _replica_platform(),
    }
    return rec


# ---------------------------------------------------------------------------
# autoscale mode (ISSUE 18): a stepped offered load (low → high → low)
# against an ELASTIC fleet — in-process FleetAutoscaler actuating real
# replica subprocesses — vs the same trace against the static
# initial-size fleet, plus a two-tenant QoS trace (bulk capped at its
# quota, the latency tenant's p99 compared with and without the flood).
# ---------------------------------------------------------------------------
def _qos_client(router, stop_at, think_s, dim, rows, seed, out, tenant):
    """Closed-loop client labelled with a tenant. Typed quota
    rejections (the bulk tenant hitting its budget) and overload sheds
    are EXPECTED and counted separately from genuine failures."""
    import numpy as np

    from mxnet_tpu.serving import FleetOverloaded, TenantQuotaExceeded

    rng = random.Random(seed)
    nrng = np.random.RandomState(seed)
    x = nrng.randn(rows, dim).astype(np.float32)
    lat, quota, overloaded, errors = [], 0, 0, []
    while time.perf_counter() < stop_at:
        if think_s > 0:
            time.sleep(rng.expovariate(1.0 / think_s))
        t0 = time.perf_counter()
        try:
            router.request("model", x, timeout=20.0, tenant=tenant)
            lat.append(time.perf_counter() - t0)
        except TenantQuotaExceeded:
            # typed rejection at admission: back off like a real bulk
            # client would (otherwise the rejection loop busy-spins and
            # the measurement charges CPU contention, not queueing, to
            # the latency tenant)
            quota += 1
            time.sleep(0.01)
        except FleetOverloaded:
            overloaded += 1
        except Exception as e:
            errors.append("%s: %s" % (type(e).__name__, e))
    out.append((lat, quota, overloaded, errors))


def _drive_phase(router, clients, seconds, think_ms, dim, rows, seed0,
                 tenant=None):
    """One load phase: ``clients`` closed-loop threads for ``seconds``;
    returns the phase record."""
    results = []
    stop_at = time.perf_counter() + seconds
    threads = [threading.Thread(
        target=_qos_client,
        args=(router, stop_at, think_ms / 1e3, dim, rows, seed0 + i,
              results, tenant)) for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    lats = sorted(x for lat, _q, _o, _e in results for x in lat)
    errors = [e for _l, _q, _o, es in results for e in es]
    return {
        "clients": clients,
        "requests": len(lats),
        "failed": len(errors),
        "failed_examples": errors[:3],
        "quota_rejected": sum(q for _l, q, _o, _e in results),
        "overloaded": sum(o for _l, _q, o, _e in results),
        "p50_ms": round(_pctl(lats, 0.50) * 1e3, 2) if lats else None,
        "p99_ms": round(_pctl(lats, 0.99) * 1e3, 2) if lats else None,
    }


def run_autoscale_mode(prefix, dim, phases, think_ms, rows,
                       autoscale, max_replicas=3):
    """One stepped-load trace against a fleet that starts at 1 replica.
    With ``autoscale`` an in-process :class:`FleetAutoscaler` reads the
    tracker and actuates replica subprocesses directly (the bench
    plays the launcher's half through the ``actuate_fn`` seam);
    without it the fleet is the static baseline. Returns the trace
    record: per-phase p50/p99 + the replica trajectory."""
    from mxnet_tpu import profiler
    from mxnet_tpu.serving import FleetRouter
    from mxnet_tpu.serving.autoscale import FleetAutoscaler
    from mxnet_tpu.tracker import Tracker

    tracker = Tracker(num_workers=0, num_servers=0)
    tracker.serve_in_background()
    procs = {0: _spawn_replica(0, tracker.addr, prefix, dim, (1, 4, 16))}
    profiler.fleet_reset()
    profiler.autoscale_reset()
    router = FleetRouter(tracker_uri=tracker.addr, view_interval=0.25,
                         timeout=20.0)
    scaler = None
    scaler_thread = None
    retired = set()

    def actuate(directive):
        # the launcher's half, in-process: retire set is the
        # autoscaler's (it drains + stops the victim itself over the
        # admin wire); scale-up spawns fresh ranks to fill desired
        retired.update(int(r) for r in directive.get("retired") or ())
        live = [r for r, p in procs.items()
                if r not in retired and p.poll() is None]
        next_rank = max(procs) + 1
        for r in range(next_rank,
                       next_rank + max(int(directive["desired"])
                                       - len(live), 0)):
            procs[r] = _spawn_replica(r, tracker.addr, prefix, dim,
                                      (1, 4, 16))

    try:
        deadline = time.monotonic() + 120
        while sum(1 for _a, s, alive, _l in router.replicas()
                  if alive and s == "serving") < 1:
            if time.monotonic() > deadline:
                raise RuntimeError("fleet never came up")
            time.sleep(0.25)
            router.refresh_view(force=True)
        if autoscale:
            scaler = FleetAutoscaler(
                tracker_uri=tracker.addr, actuate_fn=actuate,
                min_replicas=1, max_replicas=max_replicas,
                interval=0.25, up_load=2.0, down_load=0.25,
                hysteresis=2, cooldown=1.0)
            scaler_thread = threading.Thread(target=scaler.run_forever,
                                             daemon=True)
            scaler_thread.start()
        recs = []
        peak = 1
        for i, (clients, seconds) in enumerate(phases):
            rec = _drive_phase(router, clients, seconds, think_ms, dim,
                               rows, 3000 + 100 * i)
            router.refresh_view(force=True)
            serving = sum(1 for _a, s, alive, _l in router.replicas()
                          if alive and s == "serving")
            peak = max(peak, serving)
            rec["replicas_after"] = serving
            recs.append(rec)
        if autoscale:
            # let the scale-down streak + cooldown settle before
            # reading the final size
            time.sleep(4.0)
            router.refresh_view(force=True)
        final = sum(1 for _a, s, alive, _l in router.replicas()
                    if alive and s == "serving")
        out = {
            "phases": recs,
            "replicas_peak": peak,
            "replicas_final": final,
            "requests": sum(r["requests"] for r in recs),
            "failed": sum(r["failed"] for r in recs),
        }
        if autoscale:
            out["autoscale"] = profiler.autoscale_stats(reset=True)
        return out
    finally:
        if scaler is not None:
            scaler.close()
            scaler_thread.join(timeout=10)
        try:
            router.stop_fleet()
        except Exception:
            pass
        router.close()
        for p in procs.values():
            try:
                p.wait(timeout=15)
            except Exception:
                p.kill()
        tracker.shutdown()


def run_two_tenant_mode(prefix, dim, seconds, think_ms, rows,
                        bulk_req_rate=25.0):
    """The QoS half: the latency tenant's p99 measured alone, then
    with a bulk-tenant flood sharing the fleet — bulk capped at its
    request-rate quota (typed rejections at admission, never queued),
    latency priority class ahead of bulk at the broker."""
    from mxnet_tpu import profiler
    from mxnet_tpu.serving import FleetRouter, QosPolicy
    from mxnet_tpu.tracker import Tracker

    policy = QosPolicy(
        tenants={"latency": {"priority": "latency"},
                 "bulk": {"priority": "bulk",
                          "req_rate": bulk_req_rate}},
        burst_seconds=1.0)
    tracker = Tracker(num_workers=0, num_servers=0)
    tracker.serve_in_background()
    procs = [_spawn_replica(0, tracker.addr, prefix, dim, (1, 4, 16))]
    profiler.fleet_reset()
    profiler.qos_reset()
    router = FleetRouter(tracker_uri=tracker.addr, view_interval=0.5,
                         timeout=20.0, qos=policy)
    try:
        deadline = time.monotonic() + 120
        while sum(1 for _a, s, alive, _l in router.replicas()
                  if alive and s == "serving") < 1:
            if time.monotonic() > deadline:
                raise RuntimeError("fleet never came up")
            time.sleep(0.25)
            router.refresh_view(force=True)
        alone = _drive_phase(router, 4, seconds, think_ms, dim, rows,
                             5000, tenant="latency")
        profiler.qos_reset()
        results = []
        stop_at = time.perf_counter() + seconds
        threads = [threading.Thread(
            target=_qos_client,
            args=(router, stop_at, think_ms / 1e3, dim, rows, 6000 + i,
                  results, "latency")) for i in range(4)]
        threads += [threading.Thread(
            target=_qos_client,
            args=(router, stop_at, think_ms / 1e3, dim, rows, 7000 + i,
                  results, "bulk")) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        lat_lats = sorted(x for lat, _q, _o, _e in results[:4]
                          for x in lat)
        qos = profiler.qos_stats(reset=True)
        together = {
            "latency_p99_ms": round(_pctl(lat_lats, 0.99) * 1e3, 2)
            if lat_lats else None,
            "latency_requests": len(lat_lats),
            "qos": qos,
        }
        return {
            "bulk_req_rate": bulk_req_rate,
            "seconds": seconds,
            "latency_alone": alone,
            "together": together,
            "bulk_admitted": qos.get("bulk", {}).get("admitted", 0),
            "bulk_quota_rejections":
                qos.get("bulk", {}).get("quota_rejections", 0),
        }
    finally:
        try:
            router.stop_fleet()
        except Exception:
            pass
        router.close()
        for p in procs:
            try:
                p.wait(timeout=15)
            except Exception:
                p.kill()
        tracker.shutdown()


def measure_autoscale(seconds=5.0, think_ms=1.0, dim=128, hidden=256,
                      layers=4, classes=32, rows=1, max_replicas=3,
                      low_clients=2, high_clients=16):
    """The --autoscale record: the stepped trace low→high→low against
    the elastic fleet vs the static 1-replica baseline (the headline
    number is the high-phase p99 ratio), plus the two-tenant QoS
    trace. CPU-honest: the record carries the core count — on a small
    host the elastic fleet's replicas contend for the same cores and
    the p99 gap narrows."""
    from mxnet_tpu.model import save_checkpoint

    symbol, args_np = build_model(dim, hidden, layers, classes)
    tmpdir = tempfile.mkdtemp(prefix="bench_autoscale_")
    prefix = os.path.join(tmpdir, "model")
    save_checkpoint(prefix, 0, symbol,
                    {k: _nd(v) for k, v in args_np.items()}, {})
    cores = len(os.sched_getaffinity(0)) \
        if hasattr(os, "sched_getaffinity") else (os.cpu_count() or 1)
    phases = [(low_clients, seconds), (high_clients, seconds),
              (low_clients, seconds)]
    static = run_autoscale_mode(prefix, dim, phases, think_ms, rows,
                                autoscale=False,
                                max_replicas=max_replicas)
    elastic = run_autoscale_mode(prefix, dim, phases, think_ms, rows,
                                 autoscale=True,
                                 max_replicas=max_replicas)
    qos = run_two_tenant_mode(prefix, dim, seconds, think_ms, rows)
    high_e = elastic["phases"][1]["p99_ms"]
    high_s = static["phases"][1]["p99_ms"]
    return {
        "metric": "autoscale_high_phase_p99",
        "value": high_e,
        "unit": "ms",
        "static_high_p99_ms": high_s,
        "p99_ratio_vs_static": round(high_e / high_s, 3)
        if high_e and high_s else None,
        "elastic": elastic,
        "static": static,
        "two_tenant": qos,
        "phases": [{"clients": c, "seconds": s} for c, s in phases],
        "think_ms": think_ms,
        "cores": cores,
        "model": {"dim": dim, "hidden": hidden, "layers": layers},
        "replica_platform": _replica_platform(),
    }


# ---------------------------------------------------------------------------
# generate mode (ISSUE 12): continuous batching vs drain-whole-batch on
# an autoregressive decode workload — Poisson arrivals, sampled
# prompt/output lengths, tokens/s + p99 TTFT + slot occupancy.
# ---------------------------------------------------------------------------
def _sample_generate_workload(requests, rate, seed, max_prompt=32):
    """Poisson arrival times + heavy-tailed lengths. Output lengths are
    bimodal (mostly short, a long tail) — the realistic LLM shape, and
    exactly the regime where drain-whole-batch wastes slots: a batch
    runs as long as its LONGEST request while the short ones sit
    finished."""
    rng = random.Random(seed)
    t, work = 0.0, []
    for _ in range(requests):
        t += rng.expovariate(rate)
        prompt_len = rng.randint(4, max_prompt)
        out_len = rng.randint(4, 12) if rng.random() < 0.75 \
            else rng.randint(40, 64)
        work.append((t, prompt_len, out_len))
    return work


def run_generate_mode(policy, config, params, workload, slots, page_size,
                      seed=0):
    """Replay one arrival trace against a fresh GenerateServer with the
    given admission policy; returns the mode record."""
    import numpy as np

    from mxnet_tpu import profiler
    from mxnet_tpu.serving import GenerateServer

    prompt_rng = random.Random(10_000 + seed)
    profiler.generate_reset()
    with GenerateServer(config, params, slots=slots, page_size=page_size,
                        admit_policy=policy, name="bench-%s" % policy) as srv:
        # warm every compiled program outside the clock: each prefill
        # bucket the workload's prompt lengths can land in, plus the
        # decode step (ridden by the warm requests' generated tokens)
        need = {srv.predictor.pick_bucket(p) for _t, p, _o in workload}
        for bucket in sorted(need):
            warm_len = min(bucket, srv.predictor.max_ctx - 1)
            srv.generate(np.ones((warm_len,), np.int32), max_new_tokens=2)
        profiler.generate_reset()
        futures = []
        t0 = time.perf_counter()
        for t_arrive, prompt_len, out_len in workload:
            now = time.perf_counter() - t0
            if now < t_arrive:
                time.sleep(t_arrive - now)
            prompt = np.asarray(
                [prompt_rng.randrange(config.vocab)
                 for _ in range(prompt_len)], np.int32)
            futures.append(srv.submit(prompt, max_new_tokens=out_len))
        results = [f.result(timeout=600) for f in futures]
        wall = time.perf_counter() - t0
        stats = profiler.generate_stats(reset=True)
    tokens = sum(len(r["tokens"]) for r in results)
    ttfts = sorted(r["ttft_s"] for r in results)
    return {
        "policy": policy,
        "tokens_s": round(tokens / wall, 1),
        "tokens": tokens,
        "requests": len(results),
        "wall_s": round(wall, 2),
        "ttft_p50_ms": round(_pctl(ttfts, 0.50) * 1e3, 2),
        "ttft_p99_ms": round(_pctl(ttfts, 0.99) * 1e3, 2),
        "slot_occupancy": stats.get("slot_occupancy"),
        "decode_steps": stats.get("decode_steps"),
        "server_tokens_s": stats.get("tokens_s"),  # compute-time gauge
        "pages_high_water": stats.get("pages_high_water"),
        "pages_in_use_after": stats.get("pages_in_use"),
    }


def measure_generate(requests=64, rate=400.0, slots=8, page_size=16,
                     seed=0, vocab=128, d_model=64, n_heads=4, n_layers=2,
                     d_ff=128, max_len=256):
    """The --generate record: the SAME Poisson arrival trace replayed
    under continuous batching and under drain-whole-batch admission.
    Acceptance (ISSUE 12): continuous >= 2x tokens/s at equal-or-better
    p99 time-to-first-token, and every page returned after each run."""
    import jax

    from mxnet_tpu.models import transformer as tfm

    config = tfm.TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=n_heads, n_layers=n_layers,
        d_ff=d_ff, max_len=max_len,
        dtype=_generate_dtype())
    params = tfm.init_params(config, seed=seed)
    workload = _sample_generate_workload(requests, rate, seed)
    drain = run_generate_mode("drain", config, params, workload, slots,
                              page_size, seed=seed)
    cont = run_generate_mode("continuous", config, params, workload,
                             slots, page_size, seed=seed)
    rec = {
        "metric": "generate_throughput",
        "value": cont["tokens_s"],
        "unit": "tokens/s",
        "speedup_vs_drain": round(cont["tokens_s"] / drain["tokens_s"], 2)
        if drain["tokens_s"] else None,
        "continuous": cont,
        "drain": drain,
        "requests": requests,
        "arrival_rate": rate,
        "slots": slots,
        "page_size": page_size,
        "model": {"vocab": vocab, "d_model": d_model, "n_heads": n_heads,
                  "n_layers": n_layers, "d_ff": d_ff, "max_len": max_len},
        "backend": jax.default_backend(),
    }
    return rec


# ---------------------------------------------------------------------------
# prefix-share mode (ISSUE 16): a ~70%-shared-prefix Poisson trace
# replayed with the radix prefix cache off and on — p99 TTFT, exact
# prefill-token accounting, zero page leaks, byte-identical outputs.
# ---------------------------------------------------------------------------
def _sample_prefix_workload(requests, rate, seed, prefix_len, vocab,
                            share_frac=0.7, tail_lo=4, tail_hi=12,
                            free_lo=24, free_hi=48, out_len=4):
    """Poisson arrivals where ~share_frac of prompts are the SAME long
    system prefix plus a short unique tail (the multi-tenant chat /
    few-shot-prompt shape) and the rest are unrelated short prompts.
    Prompts are sampled HERE, not at replay time, so the sharing-on and
    sharing-off runs see byte-identical traces."""
    rng = random.Random(seed)
    prefix = [rng.randrange(1, vocab) for _ in range(prefix_len)]
    t, work = 0.0, []
    for _ in range(requests):
        t += rng.expovariate(rate)
        if rng.random() < share_frac:
            prompt = prefix + [rng.randrange(1, vocab)
                               for _ in range(rng.randint(tail_lo, tail_hi))]
            shared = True
        else:
            prompt = [rng.randrange(1, vocab)
                      for _ in range(rng.randint(free_lo, free_hi))]
            shared = False
        work.append((t, prompt, out_len, shared))
    return prefix, work


def run_prefix_mode(sharing, config, params, prefix, workload, slots,
                    page_size):
    """Replay one shared-prefix trace with the prefix cache off or on;
    returns (mode record, per-request output token tuples)."""
    import numpy as np

    from mxnet_tpu import profiler
    from mxnet_tpu.serving import GenerateServer

    profiler.generate_reset()
    with GenerateServer(config, params, slots=slots, page_size=page_size,
                        prefix_cache=sharing,
                        name="bench-prefix-%s" % ("on" if sharing else "off")
                        ) as srv:
        # warm every compiled program outside the clock: each full-prompt
        # prefill bucket the trace can land in plus the decode step, and
        # — when sharing — a pilot request that seeds the prefix into the
        # radix index (the steady state of a long-running server, so the
        # measured window starts warm) and one warm request per tail
        # bucket to compile the extend-tail program.
        need = {srv.predictor.pick_bucket(len(p))
                for _t, p, _o, _s in workload}
        for i, bucket in enumerate(sorted(need)):
            # distinct filler token per bucket: warm prompts must NOT
            # share a prefix with each other, or later warm buckets
            # take the extend-tail path and leave their full-prefill
            # program uncompiled until it fires inside the clock
            warm_len = min(bucket, srv.predictor.max_ctx - 3)
            srv.generate(np.full((warm_len,), 2 + i, np.int32),
                         max_new_tokens=2)
        if sharing:
            srv.clear_prefix()  # drop the warm requests' indexed pages
            seed_prompt = np.asarray(prefix + [1], np.int32)
            srv.generate(seed_prompt, max_new_tokens=2)  # seeds the index
            tails = {srv.predictor.pick_bucket(len(p) - len(prefix))
                     for _t, p, _o, s in workload if s}
            for tb in sorted(tails):
                n_tail = min(tb, srv.predictor.max_ctx - len(prefix) - 3)
                srv.generate(np.asarray(prefix + [1] * n_tail, np.int32),
                             max_new_tokens=2)
        profiler.generate_reset()
        futures = []
        t0 = time.perf_counter()
        for t_arrive, prompt, out_len, _shared in workload:
            now = time.perf_counter() - t0
            if now < t_arrive:
                time.sleep(t_arrive - now)
            futures.append(srv.submit(np.asarray(prompt, np.int32),
                                      max_new_tokens=out_len))
        results = [f.result(timeout=600) for f in futures]
        wall = time.perf_counter() - t0
        stats = profiler.generate_stats(reset=True)
        if sharing:
            srv.clear_prefix()  # release the index's refs: pool must drain
        pool = srv.predictor.pool.stats()
    outputs = [tuple(int(t) for t in r["tokens"]) for r in results]
    ttfts = sorted(r["ttft_s"] for r in results)
    return {
        "sharing": bool(sharing),
        "tokens": sum(len(o) for o in outputs),
        "requests": len(results),
        "wall_s": round(wall, 2),
        "ttft_p50_ms": round(_pctl(ttfts, 0.50) * 1e3, 2),
        "ttft_p99_ms": round(_pctl(ttfts, 0.99) * 1e3, 2),
        "decode_steps": stats.get("decode_steps"),
        "busy_s": round(stats.get("busy_seconds", 0.0), 3),
        "slot_occupancy": stats.get("slot_occupancy"),
        "prefill_tokens": stats.get("prefill_tokens"),
        "prefill_tokens_saved": stats.get("prefill_tokens_saved"),
        "prefix_hits": stats.get("prefix_hits"),
        "shared_pages": stats.get("shared_pages"),
        "prefix_evictions": stats.get("prefix_evictions"),
        "page_ref_high_water": stats.get("page_ref_high_water"),
        "pages_in_use_after": pool["in_use"],
        "page_leaks": pool["allocs"] - pool["frees"],
    }, outputs


def measure_prefix(requests=64, rate=400.0, slots=4, page_size=16, seed=0,
                   vocab=256, d_model=256, n_heads=8, n_layers=4, d_ff=4096,
                   max_len=512, prefix_len=496):
    """The --prefix-share record: the SAME shared-prefix Poisson trace
    replayed with the radix prefix cache off and on. Acceptance
    (ISSUE 16): sharing >= 3x lower p99 time-to-first-token with a
    prefill-token drop exactly equal to prefill_tokens_saved, zero page
    leaks, and byte-identical outputs."""
    import jax

    from mxnet_tpu.models import transformer as tfm

    config = tfm.TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=n_heads, n_layers=n_layers,
        d_ff=d_ff, max_len=max_len,
        dtype=_generate_dtype())
    params = tfm.init_params(config, seed=seed)
    prefix, workload = _sample_prefix_workload(requests, rate, seed,
                                               prefix_len, vocab)
    off, out_off = run_prefix_mode(False, config, params, prefix, workload,
                                   slots, page_size)
    on, out_on = run_prefix_mode(True, config, params, prefix, workload,
                                 slots, page_size)
    return {
        "metric": "prefix_ttft_p99_ms",
        "value": on["ttft_p99_ms"],
        "unit": "ms",
        "prefix_speedup": round(off["ttft_p99_ms"] / on["ttft_p99_ms"], 2)
        if on["ttft_p99_ms"] else None,
        "outputs_equal": out_on == out_off,
        "prefill_token_accounting_exact":
            on["prefill_tokens"] + on["prefill_tokens_saved"]
            == off["prefill_tokens"],
        "sharing_on": on,
        "sharing_off": off,
        "requests": requests,
        "arrival_rate": rate,
        "slots": slots,
        "page_size": page_size,
        "prefix_len": prefix_len,
        "model": {"vocab": vocab, "d_model": d_model, "n_heads": n_heads,
                  "n_layers": n_layers, "d_ff": d_ff, "max_len": max_len},
        "backend": jax.default_backend(),
    }


# ---------------------------------------------------------------------------
# spec mode (ISSUE 16): speculative decoding — k-token truncated
# self-draft proposals verified by ONE batched target extend step — vs
# plain decode on the same trace, at asserted-identical greedy outputs.
# ---------------------------------------------------------------------------
def _damp_upper_layers(params, eps=1e-3):
    """Scale the residual-branch output projections of every layer but
    the first toward zero. The result is a valid deep network whose
    upper layers contribute little — the regime (a strong shallow
    predictor inside a deep model) where a truncated self-draft has high
    acceptance. The bench does not hide this: acceptance_rate rides the
    record, and the tokens/s claim is conditional on it."""
    import numpy as np

    out = {}
    for k, v in params.items():
        v = np.asarray(v).copy()
        if k in ("attn_out_weight", "ffn_down_weight") and v.shape[0] > 1:
            v[1:] *= eps
        out[k] = v
    return out


def run_spec_mode(spec_k, config, params, workload, slots, page_size):
    """Replay one decode-heavy trace with speculative decoding off
    (spec_k=0) or on; returns (mode record, output token tuples)."""
    import numpy as np

    from mxnet_tpu import profiler
    from mxnet_tpu.serving import GenerateServer

    kw = {"spec_k": spec_k, "draft": 1} if spec_k else {"spec_k": 0}
    profiler.generate_reset()
    with GenerateServer(config, params, slots=slots, page_size=page_size,
                        name="bench-spec-k%d" % spec_k, **kw) as srv:
        # warm prefill buckets + the decode step; with spec on the warm
        # request also runs >= 1 speculative round, compiling the draft
        # prefill/decode and the batched verify program.
        need = {srv.predictor.pick_bucket(len(p)) for _t, p, _o in workload}
        for bucket in sorted(need):
            warm_len = min(bucket, srv.predictor.max_ctx - spec_k - 3)
            srv.generate(np.ones((warm_len,), np.int32),
                         max_new_tokens=spec_k + 2)
        profiler.generate_reset()
        futures = []
        t0 = time.perf_counter()
        for t_arrive, prompt, out_len in workload:
            now = time.perf_counter() - t0
            if now < t_arrive:
                time.sleep(t_arrive - now)
            futures.append(srv.submit(np.asarray(prompt, np.int32),
                                      max_new_tokens=out_len))
        results = [f.result(timeout=600) for f in futures]
        wall = time.perf_counter() - t0
        stats = profiler.generate_stats(reset=True)
        pool = srv.predictor.pool.stats()
    outputs = [tuple(int(t) for t in r["tokens"]) for r in results]
    return {
        "spec_k": spec_k,
        "tokens_s": round(sum(len(o) for o in outputs) / wall, 1),
        "tokens": sum(len(o) for o in outputs),
        "requests": len(results),
        "wall_s": round(wall, 2),
        "decode_steps": stats.get("decode_steps"),
        "spec_rounds": stats.get("spec_rounds"),
        "draft_proposed": stats.get("draft_proposed"),
        "draft_accepted": stats.get("draft_accepted"),
        "acceptance_rate": stats.get("acceptance_rate"),
        "pages_in_use_after": pool["in_use"],
    }, outputs


def measure_spec(k=6, requests=12, rate=50.0, slots=4, page_size=16,
                 seed=0, vocab=512, d_model=512, n_heads=8, n_layers=4,
                 d_ff=4096, max_len=128, out_len=48, damp=1e-3):
    """The --spec record: the SAME decode-heavy Poisson trace replayed
    with plain decode and with k-token speculative decoding (1-layer
    truncated self-draft). The target's upper layers are damped
    (_damp_upper_layers) so the self-draft's acceptance is high — the
    reported acceptance_rate is the condition the speedup depends on.
    Acceptance (ISSUE 16): spec >= 1.5x tokens/s at byte-identical
    greedy outputs."""
    import jax

    from mxnet_tpu.models import transformer as tfm

    config = tfm.TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=n_heads, n_layers=n_layers,
        d_ff=d_ff, max_len=max_len,
        dtype=_generate_dtype())
    params = _damp_upper_layers(tfm.init_params(config, seed=seed), damp)
    rng = random.Random(seed)
    t, workload = 0.0, []
    for _ in range(requests):
        t += rng.expovariate(rate)
        prompt = [rng.randrange(1, vocab) for _ in range(rng.randint(8, 16))]
        workload.append((t, prompt, out_len))
    base, out_base = run_spec_mode(0, config, params, workload, slots,
                                   page_size)
    spec, out_spec = run_spec_mode(k, config, params, workload, slots,
                                   page_size)
    return {
        "metric": "spec_tokens_s",
        "value": spec["tokens_s"],
        "unit": "tokens/s",
        "spec_speedup": round(spec["tokens_s"] / base["tokens_s"], 2)
        if base["tokens_s"] else None,
        "acceptance_rate": spec["acceptance_rate"],
        "outputs_equal": out_spec == out_base,
        "spec": spec,
        "baseline": base,
        "spec_k": k,
        "draft_layers": 1,
        "damp": damp,
        "requests": requests,
        "arrival_rate": rate,
        "slots": slots,
        "page_size": page_size,
        "model": {"vocab": vocab, "d_model": d_model, "n_heads": n_heads,
                  "n_layers": n_layers, "d_ff": d_ff, "max_len": max_len},
        "backend": jax.default_backend(),
    }


# ---------------------------------------------------------------------------
# quant mode (ISSUE 13): int8 post-training-quantized serving vs bf16
# on the same closed-loop Poisson trace — the nncase serving-throughput
# lever, measured end to end through the ModelServer.
# ---------------------------------------------------------------------------
def _train_model(symbol, dim, classes, seed=0, epochs=6, n=4096,
                 batch=256):
    """Briefly train the bench MLP on a clustered synthetic task.
    Post-TRAINING quantization assumes a trained model: random-weight
    logits are near-tied by construction, so top-1 agreement there
    measures tie-breaking noise, not quantization quality. Returns
    (trained args dict, a sample-factory for calibration/eval data)."""
    import numpy as np

    import mxnet_tpu as mx

    rng = np.random.RandomState(seed)
    centers = rng.randn(classes, dim).astype(np.float32) * 1.5

    def sample(count, sample_seed):
        r = np.random.RandomState(sample_seed)
        y = r.randint(0, classes, count)
        return (centers[y] + r.randn(count, dim).astype(np.float32),
                y.astype(np.float32))

    x, y = sample(n, seed + 1)
    mod = mx.mod.Module(symbol, context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(x, y, batch, label_name="softmax_label"),
            num_epoch=epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            initializer=mx.initializer.Xavier())
    args, _aux = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}, sample


def measure_quant(clients=24, seconds=5.0, think_ms=2.0, dim=256,
                  hidden=512, layers=6, classes=64, rows=8,
                  calib_batches=8, ladder=None, corpus_rows=2048):
    """The --quant record: the SAME closed-loop Poisson load served at
    bf16 and at int8 (post-training quantized through the IR pass),
    plus int8-vs-bf16 top-1 agreement on a fixed logits corpus.
    Acceptance (ISSUE 13): int8 req/s beats bf16 at equal-or-better
    p99, agreement >= 99%."""
    import jax
    import numpy as np

    from mxnet_tpu import profiler
    from mxnet_tpu.serving import AOTPredictor, env_batch_ladder

    ladder = env_batch_ladder() if ladder is None else ladder
    symbol, _raw = build_model(dim, hidden, layers, classes)
    args_np, sample = _train_model(symbol, dim, classes)
    calib = [{"data": sample(64, 500 + i)[0]} for i in range(calib_batches)]

    # fixed logits corpus: int8-vs-bf16 top-1 agreement (predictor-level,
    # outside the load loop) + accuracy of both against the labels
    corpus, labels = sample(corpus_rows, 900)
    shapes = {"data": (1, dim)}
    profiler.pass_reset()
    pred_bf16 = AOTPredictor(symbol, args_np, data_shapes=shapes,
                             ladder=(corpus_rows,), dtype="bfloat16")
    pred_int8 = AOTPredictor(symbol, args_np, data_shapes=shapes,
                             ladder=(corpus_rows,), quant="int8",
                             calib_data=calib)
    top_bf16 = np.argmax(pred_bf16.predict(corpus)[0], 1)
    top_int8 = np.argmax(pred_int8.predict(corpus)[0], 1)
    agreement = float((top_int8 == top_bf16).mean())
    acc_bf16 = float((top_bf16 == labels).mean())
    acc_int8 = float((top_int8 == labels).mean())
    pass_stats = profiler.pass_stats(reset=True)
    calib_report = (pred_int8.quant_report or {}).get("calibration", {})

    common = dict(ladder=ladder, clients=clients, seconds=seconds,
                  think_ms=think_ms, dim=dim, rows=rows, warm_ladder=True)
    bf16 = run_mode(symbol, args_np, dtype="bfloat16", **common)
    int8 = run_mode(symbol, args_np, quant="int8", calib=calib, **common)
    rec = {
        "metric": "quant_serving_throughput",
        "value": int8["req_s"],
        "unit": "req/s",
        "speedup_vs_bf16": round(int8["req_s"] / bf16["req_s"], 2)
        if bf16["req_s"] else None,
        "int8": int8,
        "bf16": bf16,
        "agreement_top1": round(agreement, 4),
        "acc_bf16": round(acc_bf16, 4),
        "acc_int8": round(acc_int8, 4),
        "corpus_rows": corpus_rows,
        "quantized_ops": pred_int8.bind_stats.get("quantized_ops"),
        "calib_batches": len(calib),
        "calibration": {k: {"absmax": v["absmax"], "scale": v["scale"]}
                        for k, v in sorted(calib_report.items())},
        "pass_stats": pass_stats.get("passes", {}).get("quantize"),
        "ladder": list(ladder),
        "clients": clients,
        "seconds": seconds,
        "think_ms": think_ms,
        "rows": rows,
        "model": {"dim": dim, "hidden": hidden, "layers": layers,
                  "classes": classes},
        "backend": jax.default_backend(),
    }
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--seconds", type=float, default=6.0,
                    help="measured window per configuration")
    ap.add_argument("--think-ms", type=float, default=1.0,
                    help="mean exponential think time per client")
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--rows", type=int, default=1,
                    help="rows per request")
    ap.add_argument("--deadline-ms", type=float, default=25.0,
                    help="per-request deadline for the overload "
                         "measurement (0 disables it)")
    ap.add_argument("--fleet", action="store_true",
                    help="fleet mode (ISSUE 11): req/s scaling 1→"
                         "--replicas replica PROCESSES behind a "
                         "FleetRouter, with a mid-run replica SIGKILL")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--autoscale", action="store_true",
                    help="autoscale mode (ISSUE 18): stepped load "
                         "low→high→low against an elastic fleet "
                         "(in-process FleetAutoscaler actuating "
                         "replica subprocesses) vs the static "
                         "1-replica baseline, plus a two-tenant QoS "
                         "trace — bulk capped at its quota, latency "
                         "tenant p99 with and without the flood")
    ap.add_argument("--generate", action="store_true",
                    help="generate mode (ISSUE 12): autoregressive "
                         "decode under Poisson arrivals — continuous "
                         "batching vs drain-whole-batch tokens/s, p99 "
                         "TTFT, slot occupancy")
    ap.add_argument("--requests", type=int, default=64,
                    help="generate mode: arrivals per measured window")
    ap.add_argument("--rate", type=float, default=400.0,
                    help="generate mode: Poisson arrival rate (req/s) — "
                         "the default offered load exceeds this host "
                         "class's decode capacity on purpose: the "
                         "continuous-vs-drain gap is an occupancy "
                         "property, visible only when the decode loop, "
                         "not the arrival process, is the bottleneck")
    ap.add_argument("--slots", type=int, default=8,
                    help="generate mode: decode batch slots")
    ap.add_argument("--page-size", type=int, default=16,
                    help="generate mode: tokens per KV page")
    ap.add_argument("--prefix-share", action="store_true",
                    help="prefix mode (ISSUE 16): ~70%% shared-prefix "
                         "Poisson trace replayed with the radix prefix "
                         "cache off and on — p99 TTFT, exact prefill-"
                         "token accounting, zero page leaks, identical "
                         "outputs")
    ap.add_argument("--spec", type=int, default=0, metavar="K",
                    help="spec mode (ISSUE 16): speculative decoding "
                         "with K-token 1-layer self-draft proposals vs "
                         "plain decode on the same trace — tokens/s, "
                         "acceptance rate, outputs asserted identical")
    ap.add_argument("--quant", choices=("int8",), default=None,
                    help="quant mode (ISSUE 13): int8 post-training-"
                         "quantized serving vs bf16 on the same Poisson "
                         "trace — req/s, p99, and top-1 agreement on a "
                         "fixed logits corpus")
    ap.add_argument("--calib-batches", type=int, default=8,
                    help="quant mode: calibration batches")
    args = ap.parse_args()
    if args.quant:
        rec = measure_quant(clients=args.clients, seconds=args.seconds,
                            think_ms=args.think_ms,
                            calib_batches=args.calib_batches,
                            rows=max(args.rows, 8))
    elif args.prefix_share:
        rec = measure_prefix(requests=args.requests, rate=args.rate,
                             slots=args.slots, page_size=args.page_size)
    elif args.spec:
        rec = measure_spec(k=args.spec, page_size=args.page_size)
    elif args.generate:
        rec = measure_generate(requests=args.requests, rate=args.rate,
                               slots=args.slots, page_size=args.page_size)
    elif args.autoscale:
        rec = measure_autoscale(seconds=args.seconds,
                                think_ms=args.think_ms, dim=args.dim,
                                hidden=args.hidden, layers=args.layers,
                                rows=args.rows,
                                max_replicas=args.replicas)
    elif args.fleet:
        rec = measure_fleet(replicas=args.replicas, clients=args.clients,
                            seconds=args.seconds, think_ms=args.think_ms,
                            dim=args.dim, hidden=args.hidden,
                            layers=args.layers, rows=args.rows)
    else:
        rec = measure(clients=args.clients, seconds=args.seconds,
                      think_ms=args.think_ms, dim=args.dim,
                      hidden=args.hidden, layers=args.layers,
                      rows=args.rows, deadline_ms=args.deadline_ms)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
