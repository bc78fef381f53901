"""Profiler — one span primitive, Chrome trace-event dumps, always-on counters.

Reference counterpart: ``src/engine/profiler.{h,cc}`` +
``python/mxnet/profiler.py`` (SURVEY §5.1). TPU-native design: the device's
truth is the JAX/XLA profiler's trace (XPlane → xprof / TensorBoard), and the
program's own spans are written into that same trace, so host and device
share one clock.  The reference's Chrome ``trace.json`` stays for API parity.

``span(name, **args)`` is the only way the program opens a span.  It enters a
``jax.profiler.TraceAnnotation``: whenever a ``jax.profiler`` trace is running
(a benchmark's traced run, or ``profiler_set_state('run')`` with
``MXNET_TPU_JAX_TRACE_DIR`` set) the span lands on the host plane of the same
``.xplane.pb`` as the device's operations.  While ``profiler_set_state('run')``
is on it also appends the Chrome event ``dump_profile`` writes.  Off, it costs
one test of ``_STATE["running"]`` and the annotation's own inactive path.

Names are fixed strings ``mx.<layer>.<what>``; arguments carry identity
(``epoch``, ``nbatch``, ``rid``, ``slot``, ``bucket``, ``tokens``, ``active``,
``step``, ``generation``), never free text.  Every span of the program:

==========================  ==================================================
``mx.executor.forward``     ``Executor.forward``: one compiled program
``mx.executor.backward``    ``Executor.backward``
``mx.executor.forward_backward``  ``Executor.forward_backward``
``mx.nd.operator``          one imperative operator, ``mode="all"`` only (op)
``mx.fit.batch``            one turn of ``fit``'s batch loop (epoch, nbatch)
``mx.fit.forward_backward`` ``forward_backward(data_batch)``
``mx.fit.stage``            ``DeviceQueueIter``'s worker waiting for a staging
                            slot and copying one host batch array's rows
                            into it (name, nbytes)
``mx.fit.h2d``              one batch array really copied to the mesh
                            (name, nbytes)
``mx.fit.dispatch``         the call into the compiled train step (step)
``mx.fit.throttle``         dispatch-ahead bound blocking on the oldest step
``mx.fit.update``           ``update()``
``mx.fit.next_batch``       ``next(data_iter)`` and ``prepare``
``mx.fit.feed_wait``        ``DeviceQueueIter.next`` waiting for its worker's
                            next placed batch
``mx.fit.update_metric``    ``update_metric`` (asynchronous on the
                            device-metrics path)
``mx.metric.drain``         ``EvalMetric.get``'s one blocking read of the
                            device-resident sums (sources)
``mx.fit.host_sync``        the per-batch blocking read of the host-fallback
                            metric path
``mx.fit.callbacks``        the ``batch_end_callback`` loop
``mx.fit.epoch_end``        ``get_params`` / ``set_params`` after an epoch
                            (epoch)
``mx.serve.submit``         ``GenerateServer.submit``, client thread
                            (rid, prompt_tokens)
``mx.serve.loop``           one turn of the broker loop (active, queued)
``mx.serve.admit``          admission up to the first prefill (admitted)
``mx.serve.prefill``        one request's prefill (rid, slot, prompt_tokens,
                            bucket, prefix_len, queue_wait_ms, active)
``mx.serve.prefill.device`` the predictor's prefill call, dispatch to logits
``mx.serve.grow_pages``     page growth before a decode step is dispatched
``mx.serve.decode.device``  a turn of the decode loop, which keeps a step in
                            flight, up to the ids on the host: the next step
                            dispatched, the step before read (a speculative
                            round: dispatch to logits, step, active)
``mx.serve.decode.dispatch``  step ``step`` dispatched for ``active`` slots
``mx.serve.decode.read``    the wait for the ids step ``step`` chose on the
                            device: every step run is in one dispatch span
                            and one read span of the same ``step``
``mx.serve.decode.sample``  the read step's ids appended, streamed, finished,
                            all slots (a speculative round: argmax too)
``mx.serve.finish``         a request leaves its slot (rid, reason, tokens)
``mx.serve.wait_work``      the broker idle, waiting for a request: one slice
                            of at most 50 ms, a notify ends it at once
``mx.host.gc``              one collection of Python's garbage collector,
                            on whichever thread triggered it (generation,
                            collected)
==========================  ==================================================

Inside the compiled programs the same naming is ``jax.named_scope`` metadata
(``mx.lm.embed``, ``mx.lm.attn``, ``mx.lm.ffn``, ``mx.lm.head_loss``,
``mx.opt.update``, ``mx.step.forward``, ``mx.step.backward``,
``mx.gen.pool_write``, ``mx.gen.attn``, and ``mx.gen.gather_kv`` where a
program still gathers a slot's pages: extend, and decode off the TPU) and the
kernels' own names (``mx_flash_fwd``, ``mx_flash_dq``, ``mx_flash_dkv``,
``mx_paged_decode``).  The latent-attention expert model
(``models/mla_moe.py``) adds ``mx.gen.latent_proj`` (the low-rank query and
key-value projections), ``mx.gen.index`` (the indexer: its projections, the
index scores and the top-k), ``mx.lm.moe.route``, ``mx.lm.moe.experts`` and
``mx.lm.moe.shared``; its ``mx.gen.attn`` is the sparse absorbed attention
with the gather of the selected latent rows (and, where the attention is
gated or has sinks, the gate's multiply and the sink; the gate's projection
is under ``mx.gen.latent_proj``), and ``mx.lm.hc`` holds a hyper-connected
residual path's maps (the norm over the streams, their projection, Sinkhorn,
the reads and writes of the streams).  The shortcut-connected double
block (``models/scmoe.py``) has the same names without the indexer and the
shared expert, ``mx.lm.moe.zero`` (the identity experts' term) and, under
``mx.gen.attn``, the kernel ``mx_mla_paged_decode`` over every cached row.
"""
from __future__ import annotations

import gc
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation as _Annotation

_STATE = {
    "mode": "symbolic",
    "filename": "profile.json",
    "running": False,
    "events": [],
    "jax_trace_dir": None,
}
# reentrant: a collection can start inside any ``with _LOCK`` body, and its
# span takes the lock on the same thread
_LOCK = threading.RLock()


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """ref: MXSetProfilerConfig (modes symbolic|all)."""
    _STATE["mode"] = mode
    _STATE["filename"] = filename


def profiler_set_state(state="stop"):
    """ref: MXSetProfilerState — 'run' starts collection, 'stop' ends it."""
    if state == "run" and not _STATE["running"]:
        _STATE["running"] = True
        _STATE["events"] = []
        tdir = os.environ.get("MXNET_TPU_JAX_TRACE_DIR")
        if tdir:
            import jax

            jax.profiler.start_trace(tdir)
            _STATE["jax_trace_dir"] = tdir
    elif state == "stop" and _STATE["running"]:
        _STATE["running"] = False
        if _STATE["jax_trace_dir"]:
            import jax

            jax.profiler.stop_trace()
            _STATE["jax_trace_dir"] = None


set_config = profiler_set_config
set_state = profiler_set_state


class _ChromeSpan:
    """A span while ``profiler_set_state('run')`` is on: the annotation, and
    the Chrome event ``dump_profile`` writes (its category is the last part
    of the span's name; an ``op`` argument names the event, as the
    reference's operator events are named)."""

    __slots__ = ("_ann", "_name", "_args", "_start")

    def __init__(self, name, args):
        self._ann = _Annotation(name, **args)
        self._name, self._args = name, args

    def __enter__(self):
        self._start = time.perf_counter_ns()
        self._ann.__enter__()
        return self

    def set_metadata(self, **args):
        self._ann.set_metadata(**args)
        self._args.update(args)

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        end = time.perf_counter_ns()
        event = {"name": self._args.get("op", self._name),
                 "cat": self._name.rpartition(".")[2], "ph": "X",
                 "ts": self._start // 1000, "dur": (end - self._start) // 1000,
                 "pid": os.getpid(), "tid": threading.get_ident(),
                 "args": self._args}
        with _LOCK:
            _STATE["events"].append(event)
        return False


def span(name, /, **args):
    """Open the span ``name`` (a row of the table above) as a context
    manager; ``set_metadata(**args)`` adds arguments known only inside."""
    if _STATE["running"]:
        return _ChromeSpan(name, args)
    return _Annotation(name, **args)


_GC_SPAN = [None]   # the collection open now: one runs at a time, GIL held


def _on_gc(phase, info):
    """``gc.callbacks``: each collection as the span ``mx.host.gc``."""
    if phase == "start":
        _GC_SPAN[0] = span("mx.host.gc", generation=info["generation"])
        _GC_SPAN[0].__enter__()
    elif _GC_SPAN[0] is not None:
        s, _GC_SPAN[0] = _GC_SPAN[0], None
        s.set_metadata(collected=info["collected"])
        s.__exit__(None, None, None)


gc.callbacks.append(_on_gc)


def all_operators():
    """Whether every imperative operator is stamped (ref: kAllOperator,
    src/engine/profiler.h:97-98): running, in ``mode="all"``."""
    return _STATE["running"] and _STATE["mode"] == "all"


def dump_profile():
    """ref: MXDumpProfile → Chrome trace-event JSON (profiler.h:137-139)."""
    with _LOCK:
        payload = {"traceEvents": list(_STATE["events"]), "displayTimeUnit": "ms"}
    comm = comm_stats()
    if comm:
        # comms counters ride along in the trace dump (Chrome ignores
        # unknown top-level keys) so one artifact captures both views
        payload["commStats"] = comm
    pipe = pipeline_stats()
    if pipe:
        payload["pipelineStats"] = pipe
    serve = serving_stats()
    if serve:
        payload["servingStats"] = serve
    mem = memory_stats()
    if mem:
        payload["memoryStats"] = mem
    health = health_stats()
    if health:
        payload["healthStats"] = health
    tuning = tuning_stats()
    if tuning:
        payload["tuningStats"] = tuning
    fleet = fleet_stats()
    if fleet:
        payload["fleetStats"] = fleet
    gen = generate_stats()
    if gen:
        payload["generateStats"] = gen
    passes = pass_stats()
    if passes:
        payload["passStats"] = passes
    embed = embedding_stats()
    if embed:
        payload["embeddingStats"] = embed
    io = io_stats()
    if io:
        payload["ioStats"] = io
    autoscale = autoscale_stats()
    if autoscale:
        payload["autoscaleStats"] = autoscale
    qos = qos_stats()
    if qos:
        payload["qosStats"] = qos
    mp = mp_stats()
    if mp:
        payload["mpStats"] = mp
    with open(_STATE["filename"], "w") as f:
        json.dump(payload, f)


# ---------------------------------------------------------------------------
# comms observability (ISSUE 4): always-on per-op counters for the
# distributed data plane — raw (pre-compression) vs wire bytes, RPC
# latency, in-flight depth. Cheap enough to run unconditionally; the
# Chrome-trace events above stay gated on the profiler running.
# ---------------------------------------------------------------------------
_COMM_LOCK = threading.Lock()
_COMM = {}


def comm_record(op, raw_bytes=0, wire_bytes=0, seconds=0.0, count=0,
                inflight=0):
    """Accumulate comms counters for one kvstore op family."""
    with _COMM_LOCK:
        s = _COMM.get(op)
        if s is None:
            s = _COMM[op] = {"count": 0, "raw_bytes": 0, "wire_bytes": 0,
                             "seconds": 0.0, "max_inflight": 0}
        s["count"] += count
        s["raw_bytes"] += raw_bytes
        s["wire_bytes"] += wire_bytes
        s["seconds"] += seconds
        if inflight > s["max_inflight"]:
            s["max_inflight"] = inflight


def comm_stats(reset=False):
    """Snapshot of the per-op comms counters, with derived avg_ms (and,
    where raw bytes were recorded, the compression ratio)."""
    with _COMM_LOCK:
        snap = {op: dict(s) for op, s in _COMM.items()}
        if reset:
            _COMM.clear()
    for s in snap.values():
        if s["count"]:
            s["avg_ms"] = round(s["seconds"] / s["count"] * 1e3, 3)
        if s["raw_bytes"] and s["wire_bytes"]:
            s["wire_reduction"] = round(s["raw_bytes"] / s["wire_bytes"], 2)
    return snap


def comm_reset():
    with _COMM_LOCK:
        _COMM.clear()


# ---------------------------------------------------------------------------
# input-pipeline observability (ISSUE 5): always-on counters for the
# host→device feed path and the fit hot loop. `puts`/`nbytes` count the
# actual device_put transfers (on the DeviceQueueIter worker thread when
# the async pipeline is active); `preplaced` counts batch arrays that
# arrived on the mesh already sharded (the pipelined fast path);
# `host_syncs` counts blocking device→host materializations *in the
# steady-state fit loop* — the acceptance number for a stall-free loop
# is host_syncs == 0; `stall_feed`/`stall_compute` split consumer wait
# time between "waiting on the feed queue" and "throttling dispatch
# ahead of the device". `metric_drains` counts the reads of the
# device-resident metric sums that had something to fetch
# (`EvalMetric.get` from a callback: a loop can block in one every batch
# while `host_syncs` stays 0), and `sync_seconds` is the host time spent
# blocked in both kinds of read, on the clock `put_seconds` is on.
# `staged` counts the puts whose bytes the DeviceQueueIter worker first
# copied into a reused host staging slot (`staged_share` = staged / puts),
# and `stage_wait_seconds` the time it waited for a slot's last transfer
# before writing the slot again (ISSUE 34).
# ---------------------------------------------------------------------------
_PIPE_LOCK = threading.Lock()
_PIPE_ZERO = {
    "puts": 0, "preplaced": 0, "batches": 0, "steps": 0, "nbytes": 0,
    "put_seconds": 0.0, "stall_feed_seconds": 0.0,
    "stall_compute_seconds": 0.0, "host_syncs": 0,
    "metric_drains": 0, "sync_seconds": 0.0,
    "max_queue_depth": 0, "max_inflight": 0,
    "staged": 0, "stage_wait_seconds": 0.0,
}
_PIPE = dict(_PIPE_ZERO)


def h2d_record(nbytes=0, puts=0, preplaced=0, batches=0, steps=0,
               seconds=0.0, stall_feed=0.0, stall_compute=0.0,
               queue_depth=None, inflight=None, host_syncs=0,
               metric_drains=0, sync_seconds=0.0, staged=0, stage_wait=0.0):
    """Accumulate input-pipeline counters (thread-safe; cheap enough to
    run unconditionally, like comm_record)."""
    with _PIPE_LOCK:
        s = _PIPE
        s["puts"] += puts
        s["preplaced"] += preplaced
        s["batches"] += batches
        s["steps"] += steps
        s["nbytes"] += nbytes
        s["put_seconds"] += seconds
        s["stall_feed_seconds"] += stall_feed
        s["stall_compute_seconds"] += stall_compute
        s["host_syncs"] += host_syncs
        s["metric_drains"] += metric_drains
        s["sync_seconds"] += sync_seconds
        s["staged"] += staged
        s["stage_wait_seconds"] += stage_wait
        if queue_depth is not None and queue_depth > s["max_queue_depth"]:
            s["max_queue_depth"] = queue_depth
        if inflight is not None and inflight > s["max_inflight"]:
            s["max_inflight"] = inflight


def pipeline_stats(reset=False):
    """Snapshot of the input-pipeline counters with derived averages.
    Empty dict when nothing was recorded."""
    with _PIPE_LOCK:
        snap = dict(_PIPE)
        if reset:
            _PIPE.update(_PIPE_ZERO)
    if not any(snap[k] for k in ("puts", "preplaced", "batches", "steps",
                                 "host_syncs", "metric_drains")):
        return {}
    if snap["puts"]:
        snap["avg_put_ms"] = round(
            snap["put_seconds"] / snap["puts"] * 1e3, 3)
        if snap["put_seconds"] > 0:
            snap["put_MBps"] = round(
                snap["nbytes"] / snap["put_seconds"] / 1e6, 1)
        snap["staged_share"] = snap["staged"] / snap["puts"]
    if snap["batches"]:
        snap["avg_stall_feed_ms"] = round(
            snap["stall_feed_seconds"] / snap["batches"] * 1e3, 3)
    if snap["steps"]:
        snap["avg_stall_compute_ms"] = round(
            snap["stall_compute_seconds"] / snap["steps"] * 1e3, 3)
    return snap


def pipeline_reset():
    with _PIPE_LOCK:
        _PIPE.update(_PIPE_ZERO)


# ---------------------------------------------------------------------------
# serving observability (ISSUE 6): always-on per-model counters for the
# serving tier — request/batch counts, batch-fill ratio (rows actually
# served / bucket capacity dispatched), queue depth, and a bounded
# latency reservoir for p50/p99. Cheap enough to run unconditionally,
# like comm_record/h2d_record.
# ---------------------------------------------------------------------------
_SERVE_LOCK = threading.Lock()
_SERVE = {}
_SERVE_LAT_CAP = 8192  # newest-N latency reservoir per model


def serving_record(model, requests=0, batches=0, rows=0, capacity=0,
                   errors=0, shed=0, queue_depth=None, latencies=None):
    """Accumulate serving counters for one model (thread-safe).
    ``shed`` counts deadline-expired requests dropped at dequeue
    (ISSUE 9 overload shedding) — they never occupy a batch slot."""
    with _SERVE_LOCK:
        s = _SERVE.get(model)
        if s is None:
            from collections import deque

            s = _SERVE[model] = {
                "requests": 0, "batches": 0, "rows": 0, "capacity": 0,
                "errors": 0, "shed": 0, "max_queue_depth": 0,
                "lat": deque(maxlen=_SERVE_LAT_CAP)}
        s["requests"] += requests
        s["batches"] += batches
        s["rows"] += rows
        s["capacity"] += capacity
        s["errors"] += errors
        s["shed"] += shed
        if queue_depth is not None and queue_depth > s["max_queue_depth"]:
            s["max_queue_depth"] = queue_depth
        if latencies:
            s["lat"].extend(latencies)


def _percentile_ms(sorted_secs, q):
    idx = int(round(q * (len(sorted_secs) - 1)))
    return round(sorted_secs[idx] * 1e3, 3)


def serving_stats(reset=False):
    """Per-model snapshot with derived batch-fill ratio, mean batch
    size, and p50/p99 request latency (ms). Empty dict when the serving
    tier never ran."""
    with _SERVE_LOCK:
        # lat copied to a list INSIDE the lock: handing the live deque
        # out would race serving_record's extend during sorted()
        snap = {m: dict(s, lat=list(s["lat"])) for m, s in _SERVE.items()}
        if reset:
            _SERVE.clear()
    out = {}
    for model, s in snap.items():
        lat = sorted(s.pop("lat"))
        if s["batches"]:
            s["avg_batch_rows"] = round(s["rows"] / s["batches"], 2)
        if s["capacity"]:
            s["batch_fill"] = round(s["rows"] / s["capacity"], 3)
        if lat:
            s["p50_ms"] = _percentile_ms(lat, 0.50)
            s["p99_ms"] = _percentile_ms(lat, 0.99)
        out[model] = s
    return out


def serving_reset():
    with _SERVE_LOCK:
        _SERVE.clear()


# ---------------------------------------------------------------------------
# memory observability (ISSUE 7): a GAUGE (latest snapshot, not an
# accumulator) of the training carry's per-device residency — measured
# param/opt-state/aux bytes on this process's first mesh device plus the
# analytic gradient/collective per-step estimates. Published by
# TrainStep.place()/record_memory_stats; the ZeRO acceptance assert
# (per-device opt bytes scale 1/N) reads exactly this surface.
# ---------------------------------------------------------------------------
_MEM_LOCK = threading.Lock()
_MEM = {}


def memory_record(**fields):
    """Replace the memory gauge with the latest snapshot's fields."""
    with _MEM_LOCK:
        _MEM.clear()
        _MEM.update(fields)


def memory_stats(reset=False):
    """Latest memory snapshot ({} when no carry was ever placed)."""
    with _MEM_LOCK:
        snap = dict(_MEM)
        if reset:
            _MEM.clear()
    return snap


def memory_reset():
    with _MEM_LOCK:
        _MEM.clear()


# ---------------------------------------------------------------------------
# tensor-parallel observability (ISSUE 20): a GAUGE like memoryStats —
# the latest snapshot of the mp execution's memory/collective shape:
# mesh split (dp x mp), serving group size, MEASURED per-chip parameter
# and live (compiled peak) bytes, and the per-step collective bill
# (psums per block is the megatron contract: exactly 2 — asserted exact
# in tests/test_model_parallel.py via block_collective_counts). Rides
# dump_profile as mpStats. Unknown counter names raise (the
# fleet_record rule: a typo'd counter must not silently vanish from
# the acceptance evidence).
# ---------------------------------------------------------------------------
_MP_LOCK = threading.Lock()
_MP_KEYS = frozenset((
    "mp_size", "dp_size", "group_size",
    "param_bytes_per_chip", "live_bytes_per_chip",
    "psum_per_block", "psum_outside", "all_gather_per_step",
    "collectives_per_step",
))
_MP = {}


def mp_record(**fields):
    """Update the tensor-parallel gauge with the latest snapshot's
    fields (partial updates merge). Unknown counter names raise."""
    with _MP_LOCK:
        for k, v in fields.items():
            if k not in _MP_KEYS:
                raise ValueError("mp_record: unknown counter %r" % k)
            _MP[k] = int(v)


def mp_stats(reset=False):
    """Latest tensor-parallel snapshot ({} when mp never ran)."""
    with _MP_LOCK:
        snap = dict(_MP)
        if reset:
            _MP.clear()
    return snap


def mp_reset():
    with _MP_LOCK:
        _MP.clear()


# ---------------------------------------------------------------------------
# self-healing observability (ISSUE 9): reaction-side EVENT counters
# (rollbacks, preemptions, host-tier unhealthy checks — accumulated by
# health_record) plus the latest drained snapshot of the in-graph
# sentinel's device counters (a GAUGE like memoryStats: the counters
# themselves accumulate on device inside the compiled step, so the
# newest drain IS the cumulative truth). Rides dump_profile as
# healthStats.
# ---------------------------------------------------------------------------
_HEALTH_LOCK = threading.Lock()
_HEALTH_EVENTS = {}
_HEALTH_SENTINEL = {}


def health_record(**adds):
    """Accumulate integer reaction-side counters (rollbacks=1, ...)."""
    with _HEALTH_LOCK:
        for k, v in adds.items():
            _HEALTH_EVENTS[k] = _HEALTH_EVENTS.get(k, 0) + int(v)


def health_sentinel(snapshot):
    """Replace the sentinel gauge with the newest drained device
    counters (TrainStep/FusedSPMDGroup health_stats)."""
    with _HEALTH_LOCK:
        _HEALTH_SENTINEL.clear()
        _HEALTH_SENTINEL.update(snapshot or {})


def health_stats(reset=False):
    """{event counters..., "sentinel": latest device snapshot}; empty
    dict when neither side ever recorded."""
    with _HEALTH_LOCK:
        snap = dict(_HEALTH_EVENTS)
        sent = dict(_HEALTH_SENTINEL)
        if reset:
            _HEALTH_EVENTS.clear()
            _HEALTH_SENTINEL.clear()
    if sent:
        snap["sentinel"] = sent
    return snap


def health_reset():
    with _HEALTH_LOCK:
        _HEALTH_EVENTS.clear()
        _HEALTH_SENTINEL.clear()


# ---------------------------------------------------------------------------
# autotuner observability (ISSUE 10 + 15): always-on counters for the
# schedule-table consult path — table hits/misses (one per trace-time
# schedule_for call, memo'd thereafter per key), fallbacks (a stored
# schedule rejected as illegal for the shape), the chosen schedule per
# kernel key with its source (table vs default) — plus the learned-
# ranker counters: candidates scored, timings the ranking skipped,
# abstains (exhaustive fallback), model refits, background-tuning
# slots/commits, and a per-(kernel, backend) predicted-vs-measured
# validation rank-correlation gauge. Cheap enough to run
# unconditionally, like comm_record; rides dump_profile as
# tuningStats. Unknown counter names raise.
# ---------------------------------------------------------------------------
_TUNE_LOCK = threading.Lock()
_TUNE_ZERO = {"hits": 0, "misses": 0, "fallbacks": 0,
              "candidates_ranked": 0, "timings_skipped": 0,
              "ranker_abstains": 0, "model_refits": 0,
              "bg_slots": 0, "bg_commits": 0}
_TUNE = dict(_TUNE_ZERO)
_TUNE_KERNELS = {}
_TUNE_CORR = {}


def tuning_record(kernel=None, schedule=None, source=None, corr=None,
                  **counts):
    """Accumulate autotuner counters (``hits=1``,
    ``timings_skipped=4``, ... — unknown names raise). ``kernel`` (a
    table key) additionally records that kernel's chosen schedule +
    source; ``corr`` merges a {model group: validation rank
    correlation} gauge."""
    for name in counts:
        if name not in _TUNE_ZERO:
            raise ValueError("unknown tuning counter %r (known: %s)"
                             % (name, ", ".join(sorted(_TUNE_ZERO))))
    with _TUNE_LOCK:
        for name, v in counts.items():
            _TUNE[name] += v
        if kernel is not None:
            _TUNE_KERNELS[kernel] = {"schedule": schedule, "source": source}
        if corr:
            for gk, v in dict(corr).items():
                _TUNE_CORR[gk] = round(float(v), 4)


def tuning_stats(reset=False):
    """Snapshot {hits, misses, fallbacks, candidates_ranked,
    timings_skipped, ranker_abstains, model_refits, bg_slots,
    bg_commits, kernels: {key: {schedule, source}}, rank_correlation:
    {group: r}}; empty dict when the tuning path never ran."""
    with _TUNE_LOCK:
        snap = dict(_TUNE)
        kernels = {k: dict(v) for k, v in _TUNE_KERNELS.items()}
        corr = dict(_TUNE_CORR)
        if reset:
            _TUNE.update(_TUNE_ZERO)
            _TUNE_KERNELS.clear()
            _TUNE_CORR.clear()
    if not (any(snap.values()) or kernels or corr):
        return {}
    if kernels:
        snap["kernels"] = kernels
    if corr:
        snap["rank_correlation"] = corr
    return snap


def tuning_reset():
    with _TUNE_LOCK:
        _TUNE.update(_TUNE_ZERO)
        _TUNE_KERNELS.clear()
        _TUNE_CORR.clear()


# ---------------------------------------------------------------------------
# serving-fleet observability (ISSUE 11): router-side counters for the
# multi-replica serving tier — requests routed/completed, retries split
# by cause (never-sent failover, in-flight loss, draining rejection,
# overload shed), terminal failures, and a bounded latency reservoir
# for end-to-end (router-observed) p50/p99. Always-on like comm_record;
# rides dump_profile as fleetStats.
# ---------------------------------------------------------------------------
_FLEET_LOCK = threading.Lock()
_FLEET_ZERO = {
    "requests": 0, "completed": 0, "failed": 0, "retries": 0,
    "failovers": 0, "inflight_lost": 0, "draining_rejections": 0,
    "overload_rejections": 0, "overloaded": 0, "swaps": 0,
    "replicas_alive": 0,
}
_FLEET = dict(_FLEET_ZERO)
_FLEET_LAT_CAP = 8192
_FLEET_LAT = None  # deque, created lazily


def fleet_record(latencies=None, replicas_alive=None, **adds):
    """Accumulate router-side fleet counters (thread-safe).
    ``replicas_alive`` is a gauge (latest view size); everything else
    accumulates. Unknown counter names raise — a typo'd counter would
    silently vanish from the acceptance evidence."""
    global _FLEET_LAT
    with _FLEET_LOCK:
        for k, v in adds.items():
            if k not in _FLEET_ZERO:
                raise ValueError("fleet_record: unknown counter %r" % k)
            _FLEET[k] += int(v)
        if replicas_alive is not None:
            _FLEET["replicas_alive"] = int(replicas_alive)
        if latencies:
            if _FLEET_LAT is None:
                from collections import deque

                _FLEET_LAT = deque(maxlen=_FLEET_LAT_CAP)
            _FLEET_LAT.extend(latencies)


def fleet_stats(reset=False):
    """Snapshot of the router-side fleet counters with derived p50/p99
    (ms); empty dict when no router ever ran."""
    global _FLEET_LAT
    with _FLEET_LOCK:
        snap = dict(_FLEET)
        lat = sorted(_FLEET_LAT) if _FLEET_LAT else []
        if reset:
            _FLEET.update(_FLEET_ZERO)
            _FLEET_LAT = None
    if not any(snap.values()):
        return {}
    if lat:
        snap["p50_ms"] = _percentile_ms(lat, 0.50)
        snap["p99_ms"] = _percentile_ms(lat, 0.99)
    return snap


def fleet_reset():
    global _FLEET_LAT
    with _FLEET_LOCK:
        _FLEET.update(_FLEET_ZERO)
        _FLEET_LAT = None


# ---------------------------------------------------------------------------
# generative-serving observability (ISSUE 12): counters for the
# continuous-batching decode loop — request/prefill/decode-step/token
# counts, finish reasons (eos/length/deadline/exhausted/errors), shed
# at dequeue, slot occupancy (active-slot-steps / slot-steps — the
# continuous-batching acceptance signal), a time-to-first-token
# reservoir, and the page-pool GAUGE (in_use / high_water / pool size —
# ``pages_in_use == 0`` after a drained run is the exact-accounting
# acceptance assert). Always-on like comm_record; rides dump_profile as
# generateStats. Unknown counter names raise (the fleet_record rule).
# ---------------------------------------------------------------------------
_GEN_LOCK = threading.Lock()
_GEN_ZERO = {
    "requests": 0, "prefills": 0, "prefill_tokens": 0,
    "decode_steps": 0, "tokens": 0, "finished": 0, "eos": 0, "length": 0,
    "deadline": 0, "exhausted": 0, "errors": 0, "shed": 0,
    "slot_steps": 0, "active_slot_steps": 0, "max_queue_depth": 0,
    # host seconds (floats): prefill is the predictor's call from dispatch
    # to logits on the host, decode the dispatch of a step and the wait for
    # the ids of the one before (``busy_seconds`` in the snapshot is their
    # sum); loop = the worker's whole working time
    # outside the condition wait, so loop - busy is the host loop's own;
    # stream = inside users' stream_fn callbacks
    "prefill_seconds": 0.0, "decode_seconds": 0.0,
    "loop_seconds": 0.0, "stream_seconds": 0.0,
    # decode steps that had at least one prefill since the step before:
    # how often a gap between two tokens holds a prefill
    "decode_steps_after_prefill": 0,
    # the plain decode loop keeps one step in flight: steps dispatched
    # while the one before was still unread (over ``decode_steps`` they ride
    # generate_stats as decode_ahead_share), and ids a step chose for a slot
    # whose request had left on the token before (``eos``, a deadline, a
    # failure: seen one step late), dropped unstreamed and uncounted
    "decode_steps_ahead": 0, "decode_tokens_discarded": 0,
    # KV pages of the pool, over all decode steps: ``read`` holds a valid
    # column of an active slot (ceil((position + 1) / page_size), what the
    # paged decode kernel copies a layer), ``spanned`` is slots x pages per
    # slot (what a gather of every block table reads); their ratio rides
    # generate_stats as decode_kv_read_share
    "decode_kv_pages_read": 0, "decode_kv_pages_spanned": 0,
    # shared-prefix KV cache (ISSUE 16): admissions that matched a
    # cached prefix, pages borrowed copy-on-write, prompt tokens whose
    # prefill was skipped, and least-recently-matched evictions
    "prefix_hits": 0, "shared_pages": 0, "prefill_tokens_saved": 0,
    "prefix_evictions": 0,
    # speculative decoding (ISSUE 16): draft-proposed vs verify-accepted
    # tokens (their ratio rides generate_stats as acceptance_rate) and
    # verify rounds run
    "draft_proposed": 0, "draft_accepted": 0, "spec_rounds": 0,
    # counted on the device by a decode program that routes tokens to the
    # experts this chip holds and selects keys (``decode_counters`` of the
    # model module), summed over the step's layers: token-expert pairs that
    # fell on held experts, token-layers routed, held experts with at least
    # one pair, the pairs there would be were every held expert as full as
    # the fullest (over ``moe_pairs_held`` it rides generate_stats as
    # moe_expert_load_max_over_mean); cached index keys scored and keys
    # kept for attention, and slot-layers that attended an earlier layer's
    # keys without scoring their own; pairs that fell on identity
    # ("zero-compute") experts, and cached rows a dense latent attention read
    "moe_pairs_held": 0, "moe_tokens": 0, "moe_experts_touched": 0,
    "moe_pairs_at_max_load": 0,
    "dsa_keys_scanned": 0, "dsa_keys_selected": 0, "dsa_selections_reused": 0,
    "moe_pairs_zero": 0, "attn_rows_read": 0,
}
_GEN_FLOATS = ("prefill_seconds", "decode_seconds", "loop_seconds",
               "stream_seconds")
_GEN_GAUGES = ("pages_in_use", "pages_high_water", "pool_pages",
               "page_ref_high_water", "prefix_pages")
_GEN = dict(_GEN_ZERO)
_GEN_PAGES = {}
_GEN_TTFT_CAP = 8192
_GEN_TTFT = None  # deque, created lazily
_GEN_QUEUE_WAIT = None  # deque of submit-to-admission seconds, same cap


def _extended(reservoir, values):
    if reservoir is None:
        from collections import deque

        reservoir = deque(maxlen=_GEN_TTFT_CAP)
    reservoir.extend(values)
    return reservoir


def generate_record(queue_depth=None, ttfts=None, queue_waits=None, **adds):
    """Accumulate generative-serving counters (thread-safe). The
    ``pages_*``/``pool_pages`` names are gauges (latest pool snapshot);
    everything else accumulates. Unknown names raise."""
    global _GEN_TTFT, _GEN_QUEUE_WAIT
    with _GEN_LOCK:
        for k, v in adds.items():
            if k in _GEN_GAUGES:
                _GEN_PAGES[k] = int(v)
            elif k in _GEN_FLOATS:
                _GEN[k] += float(v)
            elif k in _GEN_ZERO:
                _GEN[k] += int(v)
            else:
                raise ValueError("generate_record: unknown counter %r" % k)
        if queue_depth is not None and queue_depth > _GEN["max_queue_depth"]:
            _GEN["max_queue_depth"] = int(queue_depth)
        if ttfts:
            _GEN_TTFT = _extended(_GEN_TTFT, ttfts)
        if queue_waits:
            _GEN_QUEUE_WAIT = _extended(_GEN_QUEUE_WAIT, queue_waits)


def generate_stats(reset=False):
    """Snapshot with derived slot occupancy and TTFT p50/p99 (ms);
    empty dict when the generative tier never ran."""
    global _GEN_TTFT, _GEN_QUEUE_WAIT
    with _GEN_LOCK:
        snap = dict(_GEN)
        pages = dict(_GEN_PAGES)
        ttft = sorted(_GEN_TTFT) if _GEN_TTFT else []
        queue_wait = sorted(_GEN_QUEUE_WAIT) if _GEN_QUEUE_WAIT else []
        if reset:
            _GEN.update(_GEN_ZERO)
            _GEN_PAGES.clear()
            _GEN_TTFT = _GEN_QUEUE_WAIT = None
    if not (any(snap.values()) or pages):
        return {}
    snap.update(pages)
    if snap["slot_steps"]:
        snap["slot_occupancy"] = round(
            snap["active_slot_steps"] / snap["slot_steps"], 3)
    snap["busy_seconds"] = snap["prefill_seconds"] + snap["decode_seconds"]
    if snap["busy_seconds"] > 0:
        # generated tokens over prefill+decode compute time — the
        # server-side throughput gauge (bench_serve reports the
        # arrival-to-completion wall-clock variant next to it)
        snap["tokens_s"] = round(snap["tokens"] / snap["busy_seconds"], 1)
    if snap["draft_proposed"]:
        # the speculative-decoding health gauge: what fraction of draft
        # proposals the target's verify step accepted
        snap["acceptance_rate"] = round(
            snap["draft_accepted"] / snap["draft_proposed"], 3)
    if ttft:
        snap["ttft_p50_ms"] = _percentile_ms(ttft, 0.50)
        snap["ttft_p99_ms"] = _percentile_ms(ttft, 0.99)
    if queue_wait:
        snap["queue_wait_count"] = len(queue_wait)
        snap["queue_wait_p50_ms"] = _percentile_ms(queue_wait, 0.50)
        snap["queue_wait_p95_ms"] = _percentile_ms(queue_wait, 0.95)
    if snap["prefills"]:
        snap["prefill_ms_avg"] = snap["prefill_seconds"] / snap["prefills"] * 1e3
    if snap["decode_steps"]:
        # the host loop's own time a decode step: everything the worker
        # did that was neither of the two predictor calls
        snap["loop_host_ms_per_step"] = (
            snap["loop_seconds"] - snap["prefill_seconds"]
            - snap["decode_seconds"]) / snap["decode_steps"] * 1e3
        snap["decode_after_prefill_share"] = (
            snap["decode_steps_after_prefill"] / snap["decode_steps"])
        snap["decode_ahead_share"] = (
            snap["decode_steps_ahead"] / snap["decode_steps"])
    if snap["decode_kv_pages_spanned"]:
        snap["decode_kv_read_share"] = (
            snap["decode_kv_pages_read"] / snap["decode_kv_pages_spanned"])
    if snap["moe_pairs_held"]:
        snap["moe_expert_load_max_over_mean"] = (
            snap["moe_pairs_at_max_load"] / snap["moe_pairs_held"])
    return snap


def generate_reset():
    generate_stats(reset=True)


# ---------------------------------------------------------------------------
# IR-pass observability (ISSUE 13): always-on counters for the graph
# pass framework — per-pass rule hits and nodes rewritten (fusion),
# folded-node counts (the shared bind-time constant-fold split),
# quantized-op counts, and a per-tensor-group calibration GAUGE
# (absmax + chosen int8 scale, latest calibration wins). Always-on
# like comm_record; rides dump_profile as passStats. Unknown counter
# names raise (the fleet_record rule).
# ---------------------------------------------------------------------------
_PASS_LOCK = threading.Lock()
_PASS_COUNTERS = ("hits", "rewritten", "folded", "quantized")
_PASS = {}
_PASS_CALIB = {}


def pass_record(pass_name, rule=None, **adds):
    """Accumulate IR-pass counters (thread-safe). ``rule`` attributes
    ``hits`` to that rule's split under the pass. Unknown counter
    names raise — a typo'd counter would silently vanish from the
    acceptance evidence."""
    with _PASS_LOCK:
        s = _PASS.get(pass_name)
        if s is None:
            s = _PASS[pass_name] = {k: 0 for k in _PASS_COUNTERS}
            s["rules"] = {}
        for k, v in adds.items():
            if k not in _PASS_COUNTERS:
                raise ValueError("pass_record: unknown counter %r" % k)
            s[k] += int(v)
        if rule is not None and adds.get("hits"):
            s["rules"][rule] = s["rules"].get(rule, 0) \
                + int(adds["hits"])


def pass_calibration(group, **fields):
    """Replace one tensor-group's calibration gauge (absmax, scale)."""
    with _PASS_LOCK:
        _PASS_CALIB[group] = dict(fields)


def pass_stats(reset=False):
    """{"passes": {name: {hits, nodes_rewritten, folded_nodes,
    quantized_ops, rules}}, "calibration": {group: gauge}}; empty dict
    when no pass ever ran."""
    with _PASS_LOCK:
        snap = {name: dict(s, rules=dict(s["rules"]))
                for name, s in _PASS.items()}
        calib = {g: dict(v) for g, v in _PASS_CALIB.items()}
        if reset:
            _PASS.clear()
            _PASS_CALIB.clear()
    if not (snap or calib):
        return {}
    passes = {}
    for name, s in snap.items():
        passes[name] = {
            "hits": s["hits"], "nodes_rewritten": s["rewritten"],
            "folded_nodes": s["folded"], "quantized_ops": s["quantized"],
            "rules": s["rules"]}
    out = {"passes": passes}
    if calib:
        out["calibration"] = calib
    return out


def pass_reset():
    with _PASS_LOCK:
        _PASS.clear()
        _PASS_CALIB.clear()


# ---------------------------------------------------------------------------
# sharded-embedding observability (ISSUE 14): always-on counters for the
# server-sharded embedding data plane — pull/push round counts, rows
# actually moved, requested vs deduplicated id counts (their ratio IS
# the dedup win the subsystem exists for), per-shard wire bytes, typed
# out-of-vocab rejections, and bounded pull/push latency reservoirs for
# p50/p99. Always-on like comm_record; rides dump_profile as
# embeddingStats. Unknown counter names raise (the fleet_record rule).
# ---------------------------------------------------------------------------
_EMBED_LOCK = threading.Lock()
_EMBED_ZERO = {
    "pulls": 0, "pushes": 0, "ids_requested": 0, "unique_ids": 0,
    "rows_pulled": 0, "rows_pushed": 0, "oov_errors": 0,
    "pull_seconds": 0.0, "push_seconds": 0.0,
}
_EMBED_FLOATS = ("pull_seconds", "push_seconds")
_EMBED = dict(_EMBED_ZERO)
_EMBED_SHARD_BYTES = {}     # shard index -> accumulated wire bytes
_EMBED_LAT_CAP = 8192
_EMBED_PULL_LAT = None      # deque, created lazily
_EMBED_PUSH_LAT = None


def embedding_record(shard_bytes=None, pull_latencies=None,
                     push_latencies=None, **adds):
    """Accumulate sharded-embedding counters (thread-safe).
    ``shard_bytes`` is a ``{shard_index: bytes}`` increment map;
    latency lists feed the bounded reservoirs. Unknown counter names
    raise — a typo'd counter would silently vanish from the acceptance
    evidence."""
    global _EMBED_PULL_LAT, _EMBED_PUSH_LAT
    with _EMBED_LOCK:
        for k, v in adds.items():
            if k in _EMBED_FLOATS:
                _EMBED[k] += float(v)
            elif k in _EMBED_ZERO:
                _EMBED[k] += int(v)
            else:
                raise ValueError(
                    "embedding_record: unknown counter %r" % k)
        if shard_bytes:
            for s, b in shard_bytes.items():
                s = int(s)
                _EMBED_SHARD_BYTES[s] = \
                    _EMBED_SHARD_BYTES.get(s, 0) + int(b)
        if pull_latencies:
            if _EMBED_PULL_LAT is None:
                from collections import deque

                _EMBED_PULL_LAT = deque(maxlen=_EMBED_LAT_CAP)
            _EMBED_PULL_LAT.extend(pull_latencies)
        if push_latencies:
            if _EMBED_PUSH_LAT is None:
                from collections import deque

                _EMBED_PUSH_LAT = deque(maxlen=_EMBED_LAT_CAP)
            _EMBED_PUSH_LAT.extend(push_latencies)


def embedding_stats(reset=False):
    """Snapshot with derived dedup ratio (unique / requested ids) and
    pull/push p50/p99 (ms); empty dict when the embedding tier never
    ran."""
    global _EMBED_PULL_LAT, _EMBED_PUSH_LAT
    with _EMBED_LOCK:
        snap = dict(_EMBED)
        shards = {str(s): b for s, b in
                  sorted(_EMBED_SHARD_BYTES.items())}
        pull_lat = sorted(_EMBED_PULL_LAT) if _EMBED_PULL_LAT else []
        push_lat = sorted(_EMBED_PUSH_LAT) if _EMBED_PUSH_LAT else []
        if reset:
            _EMBED.update(_EMBED_ZERO)
            _EMBED_SHARD_BYTES.clear()
            _EMBED_PULL_LAT = None
            _EMBED_PUSH_LAT = None
    if not (any(snap.values()) or shards):
        return {}
    if snap["ids_requested"]:
        snap["dedup_ratio"] = round(
            snap["unique_ids"] / snap["ids_requested"], 4)
    for key in _EMBED_FLOATS:
        snap[key] = round(snap[key], 4)
    if shards:
        snap["shard_bytes"] = shards
    if pull_lat:
        snap["pull_p50_ms"] = _percentile_ms(pull_lat, 0.50)
        snap["pull_p99_ms"] = _percentile_ms(pull_lat, 0.99)
    if push_lat:
        snap["push_p50_ms"] = _percentile_ms(push_lat, 0.50)
        snap["push_p99_ms"] = _percentile_ms(push_lat, 0.99)
    return snap


def embedding_reset():
    global _EMBED_PULL_LAT, _EMBED_PUSH_LAT
    with _EMBED_LOCK:
        _EMBED.update(_EMBED_ZERO)
        _EMBED_SHARD_BYTES.clear()
        _EMBED_PULL_LAT = None
        _EMBED_PUSH_LAT = None


# ---------------------------------------------------------------------------
# sharded-data-input observability (ISSUE 17): always-on counters for
# the dataset service — records/bytes actually read off disk, decode
# work, prefetch hit/miss + queue depth, shard-lease churn (grants,
# rebalances, losses, resumes with their cursors), and a bounded
# per-batch input-wait reservoir for p50/p99 (input wait is the number
# the prefetch pipeline exists to drive toward zero). Rides
# dump_profile as ioStats. Unknown counter names raise (the
# fleet_record rule).
# ---------------------------------------------------------------------------
_IO_LOCK = threading.Lock()
_IO_ZERO = {
    "records": 0, "bytes": 0, "batches": 0, "decode_tasks": 0,
    "prefetch_hits": 0, "prefetch_misses": 0,
    "leases": 0, "lease_lost": 0, "rebalanced_leases": 0,
    "shards_done": 0, "epochs": 0, "resumes": 0,
    "read_seconds": 0.0, "decode_seconds": 0.0, "wait_seconds": 0.0,
}
_IO_FLOATS = ("read_seconds", "decode_seconds", "wait_seconds")
_IO = dict(_IO_ZERO)
_IO_CURSORS = {}            # shard index -> last resume cursor seen
_IO_QUEUE_DEPTH_MAX = 0
_IO_LAT_CAP = 8192
_IO_WAIT_LAT = None         # deque of wait seconds, created lazily


def io_record(resume_cursors=None, wait_latencies=None,
              queue_depth=None, **adds):
    """Accumulate dataset-service counters (thread-safe).
    ``resume_cursors`` is a ``{shard_index: cursor}`` last-seen map,
    ``wait_latencies`` a list of per-batch input-wait seconds for the
    reservoir, ``queue_depth`` an instantaneous prefetch-queue depth
    (the max is kept). Unknown counter names raise — a typo'd counter
    would silently vanish from the acceptance evidence."""
    global _IO_WAIT_LAT, _IO_QUEUE_DEPTH_MAX
    with _IO_LOCK:
        for k, v in adds.items():
            if k in _IO_FLOATS:
                _IO[k] += float(v)
            elif k in _IO_ZERO:
                _IO[k] += int(v)
            else:
                raise ValueError("io_record: unknown counter %r" % k)
        if resume_cursors:
            for s, c in resume_cursors.items():
                _IO_CURSORS[int(s)] = int(c)
        if queue_depth is not None and queue_depth > _IO_QUEUE_DEPTH_MAX:
            _IO_QUEUE_DEPTH_MAX = int(queue_depth)
        if wait_latencies:
            if _IO_WAIT_LAT is None:
                from collections import deque

                _IO_WAIT_LAT = deque(maxlen=_IO_LAT_CAP)
            _IO_WAIT_LAT.extend(wait_latencies)


def io_stats(reset=False):
    """Snapshot with derived prefetch hit rate, last resume cursor per
    shard, and input-wait p50/p99 (ms); empty dict when the data
    service never ran."""
    global _IO_WAIT_LAT, _IO_QUEUE_DEPTH_MAX
    with _IO_LOCK:
        snap = dict(_IO)
        cursors = {str(s): c for s, c in sorted(_IO_CURSORS.items())}
        depth = _IO_QUEUE_DEPTH_MAX
        wait_lat = sorted(_IO_WAIT_LAT) if _IO_WAIT_LAT else []
        if reset:
            _IO.update(_IO_ZERO)
            _IO_CURSORS.clear()
            _IO_QUEUE_DEPTH_MAX = 0
            _IO_WAIT_LAT = None
    if not (any(snap.values()) or cursors):
        return {}
    probes = snap["prefetch_hits"] + snap["prefetch_misses"]
    if probes:
        snap["prefetch_hit_rate"] = round(
            snap["prefetch_hits"] / probes, 4)
    for key in _IO_FLOATS:
        snap[key] = round(snap[key], 4)
    if cursors:
        snap["resume_cursors"] = cursors
    if depth:
        snap["queue_depth_max"] = depth
    if wait_lat:
        snap["input_wait_p50_ms"] = _percentile_ms(wait_lat, 0.50)
        snap["input_wait_p99_ms"] = _percentile_ms(wait_lat, 0.99)
    return snap


def io_reset():
    global _IO_WAIT_LAT, _IO_QUEUE_DEPTH_MAX
    with _IO_LOCK:
        _IO.update(_IO_ZERO)
        _IO_CURSORS.clear()
        _IO_QUEUE_DEPTH_MAX = 0
        _IO_WAIT_LAT = None


# ---------------------------------------------------------------------------
# fleet autoscaler observability (ISSUE 18): control-loop counters —
# ticks, scale decisions, flap-guard holds, retire outcomes — plus
# replicas/desired gauges. One controller per fleet, so one flat dict.
# ---------------------------------------------------------------------------
_AUTOSCALE_LOCK = threading.Lock()
_AUTOSCALE_ZERO = {
    "ticks": 0, "decisions": 0, "scale_ups": 0, "scale_downs": 0,
    "holds_hysteresis": 0, "holds_cooldown": 0, "retires": 0,
    "retire_races": 0, "errors": 0,
}
_AUTOSCALE = dict(_AUTOSCALE_ZERO)
_AUTOSCALE_GAUGES = {"replicas": 0, "desired": 0}
_AUTOSCALE_SEEN = False


def autoscale_record(replicas=None, desired=None, **adds):
    """Accumulate autoscaler counters (``replicas``/``desired`` are
    gauges — assigned, not added). Unknown counter names raise."""
    global _AUTOSCALE_SEEN
    with _AUTOSCALE_LOCK:
        for k, v in adds.items():
            if k not in _AUTOSCALE_ZERO:
                raise ValueError(
                    "autoscale_record: unknown counter %r" % (k,))
            _AUTOSCALE[k] += int(v)
        if replicas is not None:
            _AUTOSCALE_GAUGES["replicas"] = int(replicas)
        if desired is not None:
            _AUTOSCALE_GAUGES["desired"] = int(desired)
        _AUTOSCALE_SEEN = True


def autoscale_stats(reset=False):
    """Snapshot (counters + gauges); empty dict when no controller
    ever ran."""
    global _AUTOSCALE_SEEN
    with _AUTOSCALE_LOCK:
        seen = _AUTOSCALE_SEEN
        snap = dict(_AUTOSCALE)
        snap.update(_AUTOSCALE_GAUGES)
        if reset:
            _AUTOSCALE.update(_AUTOSCALE_ZERO)
            _AUTOSCALE_GAUGES.update(replicas=0, desired=0)
            _AUTOSCALE_SEEN = False
    return snap if seen else {}


def autoscale_reset():
    autoscale_stats(reset=True)


# ---------------------------------------------------------------------------
# multi-tenant QoS observability (ISSUE 18): per-tenant admission
# counters (requests / admitted / quota rejections / shed-at-dequeue /
# rows) and a completion-latency reservoir for per-tenant p50/p99 —
# the numbers behind "the bulk tenant sheds before the latency
# tenant's p99 moves".
# ---------------------------------------------------------------------------
_QOS_LOCK = threading.Lock()
_QOS_ZERO = {"requests": 0, "admitted": 0, "quota_rejections": 0,
             "shed": 0, "rows": 0, "completed": 0}
_QOS_LAT_CAP = 8192
_QOS = {}


def qos_record(tenant, latencies=None, **adds):
    """Accumulate per-tenant QoS counters; ``latencies`` (seconds)
    extend the tenant's reservoir. Unknown counter names raise."""
    tenant = str(tenant)
    with _QOS_LOCK:
        s = _QOS.get(tenant)
        if s is None:
            from collections import deque

            s = _QOS[tenant] = dict(_QOS_ZERO,
                                    lat=deque(maxlen=_QOS_LAT_CAP))
        for k, v in adds.items():
            if k not in _QOS_ZERO:
                raise ValueError("qos_record: unknown counter %r" % (k,))
            s[k] += int(v)
        if latencies:
            s["lat"].extend(latencies)


def qos_stats(reset=False):
    """Per-tenant snapshot with p50/p99 (ms); empty dict when no
    tenant-labelled traffic was seen."""
    with _QOS_LOCK:
        out = {}
        for tenant, s in sorted(_QOS.items()):
            snap = {k: s[k] for k in _QOS_ZERO}
            lat = sorted(s["lat"])
            if lat:
                snap["p50_ms"] = _percentile_ms(lat, 0.50)
                snap["p99_ms"] = _percentile_ms(lat, 0.99)
            out[tenant] = snap
        if reset:
            _QOS.clear()
    return out


def qos_reset():
    with _QOS_LOCK:
        _QOS.clear()


def pause():
    _STATE["running"] = False


def resume():
    _STATE["running"] = True


def is_running():
    return _STATE["running"]


# MXNET_PROFILER_AUTOSTART (ref: profiler.cc:65): begin collecting at
# import, dump to MXNET_PROFILER_MODE's filename at interpreter exit.
if os.environ.get("MXNET_PROFILER_AUTOSTART", "0") == "1":
    import atexit

    profiler_set_config(mode=os.environ.get("MXNET_PROFILER_MODE", "symbolic"))
    profiler_set_state("run")
    atexit.register(lambda: (profiler_set_state("stop"), dump_profile()))
