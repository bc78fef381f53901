"""Dynamic-batching async request broker (the server half of ISSUE 6).

Design: one worker thread per resident model, the PR-4 ``_ShardSender``
drain-and-coalesce pattern turned 90°: clients enqueue single requests
and get a Future back immediately; the worker drains everything queued
up to the model's largest batch bucket into ONE padded forward, then
slices results back per request. Under light load a request rides alone
in the smallest bucket (lowest latency); under heavy load the queue
refills while a batch computes, so the next drain coalesces into the
largest ready bucket (highest throughput) — no artificial batching
delay in either regime.

Bounded queue depth gives backpressure: ``submit`` blocks (up to
``MXNET_SERVE_SUBMIT_TIMEOUT``) while a model's queue holds
``MXNET_SERVE_QUEUE_DEPTH`` requests, then raises. A worker-thread
death is sticky and surfaces on the next submit (the kvstore async
convention). ``close()`` stops and joins every worker with a bounded
deadline (the PR-5 ``PrefetchingIter.close`` lesson: no leaked
daemons) and fails still-queued futures loudly.

Checkpoint hot-swap reuses the PR-3/PR-5 quiesce choreography in
miniature: the swap takes the model's execution lock (waits out the
in-flight batch = drain), refreezes + refolds the weights, and
publishes them in one assignment — queued and future requests are
served by the new model, in-flight ones complete on the old one, and
nothing is dropped.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as np

from .. import chaos, profiler
from .generate import (
    GenerateError,
    GenerativePredictor,
    PagePoolExhausted,
    PrefixIndex,
    _env_nonneg_int,
    _env_strict_bool,
    _env_positive_int,
)
from .predictor import (
    AOTPredictor,
    ExecutableCache,
    ServingError,
    env_batch_ladder,
    env_positive_float,
    env_positive_int,
)

# the generate broker waits for work in slices this long, each a span
_WAIT_SLICE_S = 0.05


class DeadlineExceeded(ServingError):
    """A request's deadline expired before it was dispatched: it was
    SHED at dequeue (ISSUE 9 overload shedding) instead of occupying a
    batch slot its client had already given up on. Its future fails
    fast with this error."""


class ServerClosed(ServingError):
    """The request was REJECTED (or failed while still queued) because
    the server is shutting down — it never executed, so a fleet router
    may safely resubmit it to a different replica. Distinct from
    :class:`DeadlineExceeded` (the client gave up) and from genuine
    request failures (which must not be retried blindly)."""


class ReplicaDraining(ServerClosed):
    """Admission-time rejection from a replica in the ``draining``
    state (explicit drain RPC or rolling ``fleet_swap``): nothing was
    executed, in-flight work continues to completion, and the router is
    expected to retry the request on a different replica."""


class ServerOverloaded(ServingError):
    """Backpressure rejection: the bounded request queue stayed full
    past the submit timeout. The request never entered the queue, so
    routing it to a less-loaded replica is always safe."""


class _Request:
    __slots__ = ("inputs", "rows", "future", "t_submit", "deadline",
                 "tenant", "priority")

    def __init__(self, inputs, rows, deadline=None, tenant=None,
                 priority=1):
        self.inputs = inputs
        self.rows = rows
        self.future = Future()
        self.t_submit = time.perf_counter()
        self.deadline = deadline  # absolute time.monotonic(), or None
        self.tenant = tenant      # QoS label (ISSUE 18), or None
        self.priority = 1 if priority is None else int(priority)


class _ModelWorker:
    """One model's queue + serving thread (drain-and-coalesce)."""

    def __init__(self, name, predictor, queue_depth):
        self.name = name
        self.predictor = predictor
        self._depth = queue_depth
        self._cond = threading.Condition()
        self._q = deque()
        self._stopped = False
        self._error = None       # sticky worker-death error
        self._busy = False       # a batch is executing right now
        # quiesce lock: held around every batch forward; swap() takes it
        # to wait out the in-flight batch before republishing weights
        self._exec_lock = threading.Lock()
        self._batch_hook = None  # test seam: called before each forward
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="serve-%s" % name)
        self._thread.start()

    # -- producer side -------------------------------------------------------
    def enqueue(self, req, timeout):
        deadline = time.monotonic() + timeout
        with self._cond:
            while True:
                if self._stopped:
                    if self._error is not None:
                        raise ServingError(
                            "model %r: worker died: %r"
                            % (self.name, self._error))
                    raise ServerClosed(
                        "model %r: worker is stopped" % self.name)
                if len(self._q) < self._depth:
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise ServerOverloaded(
                        "model %r: request queue full (%d queued, "
                        "MXNET_SERVE_QUEUE_DEPTH=%d) — backpressure "
                        "timeout" % (self.name, len(self._q), self._depth))
                self._cond.wait(min(remaining, 0.1))
            self._q.append(req)
            depth = len(self._q)
            self._cond.notify_all()
        return depth

    # -- worker side ---------------------------------------------------------
    def _drain_locked(self):
        """Pop the largest ready batch: requests in priority-then-FIFO
        order while the running row total still fits the biggest
        bucket. Requests whose deadline already expired are SHED here —
        at dequeue, before they can occupy a batch slot (their clients
        have given up; an overloaded server must spend its forwards on
        requests that are still wanted). Priority classes (ISSUE 18)
        reorder only when classes actually mix: a latency request jumps
        queued bulk work, so under overload bulk waits, expires, and is
        shed by this same discipline before a latency p99 moves. The
        sort is stable — FIFO within a class — and the all-one-class
        fast path is byte-identical to the PR 9 behavior. Returns
        (reqs, rows, shed); reqs may be empty when everything queued
        had expired."""
        cap = self.predictor.max_bucket
        now = time.monotonic()
        shed, reqs, total = [], [], 0
        queue = self._q
        if len({r.priority for r in queue}) > 1:
            queue = sorted(queue, key=lambda r: r.priority)
        taken = set()
        for r in queue:
            if r.deadline is not None and now > r.deadline:
                shed.append(r)
                taken.add(id(r))
                continue
            if reqs and total + r.rows > cap:
                break
            reqs.append(r)
            taken.add(id(r))
            total += r.rows
        if taken:
            if len(taken) == len(self._q):
                self._q.clear()
            else:
                remaining = [r for r in self._q if id(r) not in taken]
                self._q.clear()
                self._q.extend(remaining)
        return reqs, total, shed

    def _run(self):
        try:
            while True:
                with self._cond:
                    while not self._q and not self._stopped:
                        self._cond.wait()
                    if self._stopped:
                        return
                    reqs, rows, shed = self._drain_locked()
                    if reqs:
                        self._busy = True
                    self._cond.notify_all()  # queue space freed
                if shed:
                    # futures fail OUTSIDE the lock: done-callbacks run
                    # inline on set_exception and must not deadlock a
                    # client that re-submits from one
                    exc = DeadlineExceeded(
                        "model %r: deadline expired before dispatch "
                        "(shed at dequeue)" % self.name)
                    for r in shed:
                        if not r.future.done():
                            r.future.set_exception(exc)
                        if r.tenant is not None:
                            profiler.qos_record(r.tenant, shed=1)
                    profiler.serving_record(self.name, shed=len(shed))
                if not reqs:
                    continue
                try:
                    self._execute(reqs, rows)
                except BaseException as e:  # bad batch — fail ITS futures,
                    for r in reqs:          # keep serving the next ones
                        if not r.future.done():
                            r.future.set_exception(e)
                    profiler.serving_record(self.name, errors=len(reqs))
                finally:
                    with self._cond:
                        self._busy = False
                        self._cond.notify_all()
        except BaseException as e:  # worker death: sticky, fail the queue
            with self._cond:
                self._error = e
                self._stopped = True
                pending = list(self._q)
                self._q.clear()
                self._cond.notify_all()
            for r in pending:
                if not r.future.done():
                    r.future.set_exception(e)

    def _execute(self, reqs, rows):
        pred = self.predictor
        bucket = pred.pick_bucket(rows)
        with self._exec_lock:
            if self._batch_hook is not None:
                self._batch_hook(reqs)
            if len(reqs) == 1 and reqs[0].rows == bucket:
                inputs = reqs[0].inputs  # exact fit: no assembly copy
            else:
                inputs = {}
                for name in pred.data_names:
                    first = reqs[0].inputs[name]
                    buf = np.zeros((bucket,) + first.shape[1:],
                                   dtype=first.dtype)
                    ofs = 0
                    for r in reqs:
                        buf[ofs:ofs + r.rows] = r.inputs[name]
                        ofs += r.rows
                    inputs[name] = buf
            outs = pred.run_bucket(inputs, bucket)
        now = time.perf_counter()
        lats, ofs = [], 0
        for r in reqs:
            res = [o[ofs:ofs + r.rows]
                   if o.ndim and o.shape[0] == bucket else o
                   for o in outs]
            ofs += r.rows
            r.future.set_result(res)
            lats.append(now - r.t_submit)
        profiler.serving_record(self.name, batches=1, rows=rows,
                                capacity=bucket, latencies=lats)

    # -- lifecycle -----------------------------------------------------------
    def stop(self):
        with self._cond:
            self._stopped = True
            self._cond.notify_all()

    def join(self, timeout):
        self._thread.join(timeout)
        return not self._thread.is_alive()

    def fail_pending(self, exc):
        with self._cond:
            pending = list(self._q)
            self._q.clear()
            self._cond.notify_all()
        for r in pending:
            if not r.future.done():
                r.future.set_exception(exc)
        return len(pending)


class ModelServer:
    """Multi-model dynamic-batching inference server.

    ::

        with ModelServer() as srv:
            srv.add_model("resnet", symbol=sym, arg_params=args,
                          aux_params=auxs,
                          data_shapes={"data": (1, 3, 224, 224)})
            fut = srv.submit("resnet", batch_np)   # -> Future
            probs = fut.result()[0]

    All resident models share one LRU of compiled executables
    (``MXNET_SERVE_MAX_EXECUTABLES``) keyed by (model, bucket, dtype);
    evictions recompile on next use, parameters stay resident.
    """

    def __init__(self, ladder=None, queue_depth=None, cache_capacity=None,
                 submit_timeout=None, dtype="float32", device=None):
        from .predictor import validate_ladder

        self._ladder = env_batch_ladder() if ladder is None \
            else validate_ladder(ladder)
        self._queue_depth = env_positive_int(
            "MXNET_SERVE_QUEUE_DEPTH", 256) if queue_depth is None \
            else int(queue_depth)
        if self._queue_depth < 1:
            raise ServingError("ModelServer: queue_depth must be >= 1, "
                               "got %d" % self._queue_depth)
        capacity = env_positive_int("MXNET_SERVE_MAX_EXECUTABLES", 32) \
            if cache_capacity is None else cache_capacity
        self._cache = ExecutableCache(capacity)
        self._submit_timeout = env_positive_float(
            "MXNET_SERVE_SUBMIT_TIMEOUT", 60.0) if submit_timeout is None \
            else float(submit_timeout)
        self._dtype = dtype
        self._device = device
        self._workers = {}
        self._lock = threading.Lock()
        self._closed = False

    # -- model residency -----------------------------------------------------
    def add_model(self, name, symbol=None, arg_params=None, aux_params=None,
                  data_shapes=None, predictor=None, **predictor_kwargs):
        """Make ``name`` resident: either hand in a prebuilt
        :class:`AOTPredictor`, or a symbol + params + data_shapes and
        the server binds one on its shared executable cache."""
        self._check_open()
        if predictor is None:
            if symbol is None or data_shapes is None:
                raise ServingError(
                    "add_model(%r): need either predictor= or "
                    "symbol=/data_shapes= (+params)" % name)
            predictor_kwargs.setdefault("ladder", self._ladder)
            predictor_kwargs.setdefault("dtype", self._dtype)
            predictor_kwargs.setdefault("device", self._device)
            predictor = AOTPredictor(
                symbol, arg_params, aux_params, data_shapes=data_shapes,
                cache=self._cache, model_name=name, **predictor_kwargs)
        if predictor.ladder is None:
            raise ServingError(
                "add_model(%r): exact-bound predictors (ladder=None) "
                "cannot serve coalesced traffic" % name)
        with self._lock:
            if name in self._workers:
                raise ServingError("model %r is already resident; use "
                                   "swap() to update its weights" % name)
            self._workers[name] = _ModelWorker(name, predictor,
                                               self._queue_depth)
        return predictor

    def models(self):
        with self._lock:
            return sorted(self._workers)

    def _worker(self, name):
        with self._lock:
            worker = self._workers.get(name)
        if worker is None:
            raise ServingError("unknown model %r (resident: %s)"
                               % (name, self.models()))
        return worker

    def _check_open(self):
        if self._closed:
            raise ServerClosed("ModelServer is closed")

    # -- request surface -----------------------------------------------------
    def submit(self, name, inputs, timeout=None, deadline=None,
               tenant=None, priority=None):
        """Enqueue one request; returns a ``concurrent.futures.Future``
        resolving to the list of output arrays (request row count).
        Blocks for queue space up to ``timeout`` (backpressure), then
        raises :class:`ServingError`. ``deadline`` (seconds from now,
        > 0) marks the request sheddable: if it is still queued when
        the deadline passes, the worker drops it at dequeue and its
        future fails fast with :class:`DeadlineExceeded` instead of
        occupying a batch slot — overload protection for clients that
        time out anyway (counted as ``shed`` in serving_stats).
        ``tenant``/``priority`` (ISSUE 18) label the request for QoS:
        lower priority dequeues first (see qos.PRIORITIES), and sheds
        of a labelled request are counted per tenant in qos_stats."""
        self._check_open()
        worker = self._worker(name)
        pred = worker.predictor
        inputs, rows = pred._normalize(inputs)
        pred.pick_bucket(rows)  # reject oversized requests in the caller
        if deadline is not None:
            deadline = float(deadline)
            if not deadline > 0:
                raise ServingError("submit: deadline must be > 0 "
                                   "seconds, got %r" % deadline)
            deadline = time.monotonic() + deadline
        req = _Request(inputs, rows, deadline=deadline, tenant=tenant,
                       priority=priority)
        depth = worker.enqueue(
            req, self._submit_timeout if timeout is None else timeout)
        profiler.serving_record(name, requests=1, queue_depth=depth)
        return req.future

    def predict(self, name, inputs, timeout=None):
        """Synchronous convenience wrapper around :meth:`submit`."""
        return self.submit(name, inputs, timeout=timeout).result()

    # -- hot swap ------------------------------------------------------------
    def swap(self, name, arg_params=None, aux_params=None,
             allow_extra=False):
        """Atomically replace a resident model's weights without
        dropping requests: waits out the in-flight batch (quiesce),
        swaps, releases — queued requests are served by the new model."""
        self._check_open()
        worker = self._worker(name)
        with worker._exec_lock:
            return worker.predictor.swap_params(
                arg_params, aux_params, allow_extra=allow_extra)

    def swap_from_checkpoint(self, name, prefix=None, epoch=None,
                             directory=None):
        """Hot-swap from a checkpoint: either the two-artifact format
        (``prefix``/``epoch``) or the newest committed checkpoint of an
        elastic-training ``CheckpointManager`` ``directory``
        (``CheckpointManager.latest()``)."""
        if (prefix is None) == (directory is None):
            raise ServingError("swap_from_checkpoint: pass exactly one "
                               "of prefix= or directory=")
        if prefix is not None:
            from ..model import load_checkpoint

            _, arg_params, aux_params = load_checkpoint(
                prefix, 0 if epoch is None else int(epoch))
        else:
            from ..checkpoint import CheckpointManager

            ckpt = CheckpointManager(directory).latest()
            if ckpt is None:
                raise ServingError(
                    "swap_from_checkpoint: no committed checkpoint "
                    "under %r" % directory)
            arg_params, aux_params = ckpt.split_weights()
        return self.swap(name, arg_params, aux_params, allow_extra=True)

    # -- observability -------------------------------------------------------
    def stats(self, reset=False):
        """Per-model serving counters (see profiler.serving_stats)."""
        return profiler.serving_stats(reset=reset)

    def pending(self):
        """Queued requests plus in-flight batches across all resident
        models — the drain observable: a draining replica admits
        nothing and waits for this to reach 0 before swapping or
        deregistering (serving/fleet.py)."""
        with self._lock:
            workers = list(self._workers.values())
        total = 0
        for w in workers:
            with w._cond:
                total += len(w._q) + (1 if w._busy else 0)
        return total

    @property
    def executable_cache(self):
        return self._cache

    # -- lifecycle -----------------------------------------------------------
    def close(self, timeout=5.0):
        """Stop and join every worker (bounded — no leaked daemons),
        fail still-queued requests with the typed :class:`ServerClosed`
        (they never executed — a router may retry them elsewhere).
        Idempotent; submits after close raise :class:`ServerClosed`."""
        if self._closed:
            return
        self._closed = True
        with self._lock:
            workers = list(self._workers.values())
        for w in workers:
            w.stop()
        deadline = time.monotonic() + timeout
        for w in workers:
            w.join(max(0.0, deadline - time.monotonic()))
        exc = ServerClosed("ModelServer closed before the request was "
                           "dispatched")
        for w in workers:
            w.fail_pending(exc)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# generative serving (ISSUE 12): continuous-batching decode loop
# ---------------------------------------------------------------------------
_REQUEST_IDS = itertools.count(1)   # process-wide, across servers


class _GenRequest:
    __slots__ = ("rid", "tokens", "max_new", "eos_id", "future", "stream_fn",
                 "t_submit", "deadline", "no_eos", "out", "pages",
                 "slot", "ttft", "unflushed", "prefix_len", "shared",
                 "draft_pages", "draft_pos", "queue_wait")

    def __init__(self, tokens, max_new, eos_id, deadline, stream_fn):
        self.rid = next(_REQUEST_IDS)
        self.tokens = tokens
        self.max_new = max_new
        self.eos_id = eos_id
        self.future = Future()
        self.stream_fn = stream_fn
        self.t_submit = time.perf_counter()
        self.deadline = deadline     # absolute time.monotonic(), or None
        self.no_eos = False          # chaos generate:stall — never sees EOS
        self.out = []
        self.pages = []
        self.slot = None
        self.ttft = None
        self.queue_wait = None       # submit to admission, seconds
        self.unflushed = []
        self.prefix_len = 0          # tokens covered by shared prefix pages
        self.shared = 0              # pages borrowed from the prefix index
        self.draft_pages = []        # draft predictor's pages (spec decode)
        self.draft_pos = 0           # next position the draft cache needs


class _Step:
    """A decode step that was dispatched and not yet read."""
    __slots__ = ("chosen", "slots", "reqs", "counts", "step")

    def __init__(self, chosen, slots, reqs, counts, step):
        self.chosen = chosen         # on the device: ids, then counters
        self.slots = slots           # the slots it ran for ...
        self.reqs = reqs             # ... and whose they were at dispatch
        self.counts = counts         # what the host counted at dispatch
        self.step = step             # its number, on its dispatch and read spans


class GenerateServer:
    """Continuous-batching autoregressive decode server (ISSUE 12).

    The structural difference from :class:`ModelServer`: a generate
    request is not one forward but a *prefill* plus an open-ended run
    of single-token decode steps, and requests finish at different
    steps. Draining whole batches would leave finished slots idle for
    the remainder of the longest request — so the decode loop here
    admits new requests into vacated batch slots EVERY decode step
    (continuous batching): admit (shedding deadline-expired requests
    at dequeue, the PR 9 rule) → prefill admitted prompts into freshly
    allocated KV pages → dispatch the next decode step over all active
    slots → read the ids the step before chose → stream, finish,
    recycle pages. ``admit_policy="drain"`` keeps the old
    drain-whole-batch behavior for the bench comparison.

    The loop keeps one decode step in flight (ISSUE 39): the decode
    program chooses each slot's token itself and the next step takes it
    from there on the device, so step n + 1 is dispatched before the
    host has read step n, and the host's read of ``slots`` ids, its
    stream callbacks and its bookkeeping run while the device does the
    next step. Positions, block tables and the active mask are the
    host's own; a slot whose answer the step in flight completes is
    left out of the step ahead. An ``eos`` or a deadline is seen one
    step late: the stray step's id is dropped (``decode_tokens_discarded``),
    never streamed, and its cache row went to a page the request still
    owned. Before a prefill the step in flight is read and streamed, and
    when the last slot leaves the loop drains it; a program that fails
    on the device fails the loop one step later. Speculative decoding
    (``spec_k``) reads logits on the host by nature and keeps its own
    synchronous round.

    Memory is paged (:class:`~.generate.PagePool`): each slot holds a
    block table naming its pages; completion returns the pages
    immediately. Pool exhaustion at admission backpressures (the
    request waits in queue); a request that can never fit — or a
    mid-decode page the pool cannot provide — fails fast with the
    typed :class:`~.generate.PagePoolExhausted`.

    Tokens stream back through the request future (resolves to
    ``{"tokens", "finish_reason", "ttft_s", "latency_s"}``); a
    ``stream_fn`` callback additionally receives token chunks every
    ``MXNET_GENERATE_STREAM_FLUSH`` decode steps.
    """

    FINISH_EOS = "eos"
    FINISH_LENGTH = "length"

    def __init__(self, config=None, params=None, predictor=None, *,
                 slots=None, page_size=None, pool_bytes=None,
                 max_steps=None, stream_flush=None, queue_depth=None,
                 submit_timeout=None, admit_policy="continuous",
                 prefix_cache=None, prefix_evict=None, spec_k=None,
                 draft=None, draft_config=None, draft_params=None,
                 device=None, cache=None, name="generate", **pred_kwargs):
        if predictor is None and (config is None or params is None):
            raise GenerateError(
                "GenerateServer: need either predictor= or "
                "config=/params=")
        # knob parsing first: a malformed knob must raise (naming the
        # knob) before any device work or thread starts
        self._prefix_on = _env_strict_bool("MXNET_GENERATE_PREFIX_CACHE") \
            if prefix_cache is None else bool(prefix_cache)
        prefix_bound = _env_nonneg_int("MXNET_GENERATE_PREFIX_EVICT") \
            if prefix_evict is None else int(prefix_evict)
        self._spec_k = _env_nonneg_int("MXNET_GENERATE_SPEC_K") \
            if spec_k is None else int(spec_k)
        draft_layers = _env_nonneg_int("MXNET_GENERATE_DRAFT") \
            if draft is None else int(draft)
        if predictor is None:
            predictor = GenerativePredictor(
                config, params, slots=slots, page_size=page_size,
                pool_bytes=pool_bytes, device=device, cache=cache,
                model_name=name, **pred_kwargs)
        self.predictor = predictor
        self.name = name
        self._prefix = PrefixIndex(predictor.page_size, prefix_bound) \
            if self._prefix_on else None
        self._draft = None
        if self._spec_k > 0:
            if draft_config is None or draft_params is None:
                if draft_layers < 1:
                    raise GenerateError(
                        "GenerateServer: speculative decoding "
                        "(MXNET_GENERATE_SPEC_K=%d) needs a draft model: "
                        "set MXNET_GENERATE_DRAFT >= 1 (self-draft layer "
                        "count) or pass draft_config=/draft_params="
                        % self._spec_k)
                from ..models.transformer import draft_from_layers

                try:
                    draft_config, draft_params = draft_from_layers(
                        predictor.config, predictor._params, draft_layers)
                except ValueError as e:
                    raise GenerateError("GenerateServer: %s" % e)
            self._draft = GenerativePredictor(
                draft_config, draft_params, slots=predictor.slots,
                page_size=predictor.page_size, pool_bytes=0,
                max_ctx=predictor.max_ctx, block_k=predictor.block_k,
                device=device, cache=cache, model_name="%s-draft" % name)
        if admit_policy not in ("continuous", "drain"):
            raise GenerateError("GenerateServer: admit_policy must be "
                                "continuous|drain, got %r" % admit_policy)
        self._policy = admit_policy
        self._max_steps = _env_positive_int("MXNET_GENERATE_MAX_STEPS") \
            if max_steps is None else int(max_steps)
        if self._max_steps < 1:
            raise GenerateError("GenerateServer: max_steps must be >= 1, "
                                "got %d" % self._max_steps)
        self._flush_every = _env_positive_int("MXNET_GENERATE_STREAM_FLUSH") \
            if stream_flush is None else int(stream_flush)
        if self._flush_every < 1:
            raise GenerateError("GenerateServer: stream_flush must be "
                                ">= 1, got %d" % self._flush_every)
        self._depth = env_positive_int("MXNET_SERVE_QUEUE_DEPTH", 256) \
            if queue_depth is None else int(queue_depth)
        self._submit_timeout = env_positive_float(
            "MXNET_SERVE_SUBMIT_TIMEOUT", 60.0) if submit_timeout is None \
            else float(submit_timeout)

        S, MP = predictor.slots, predictor.max_pages_per_slot
        self._slot_req = [None] * S
        self._block_tables = np.zeros((S, MP), np.int32)
        self._positions = np.zeros((S,), np.int32)
        self._tokens = np.zeros((S,), np.int32)
        self._active = np.zeros((S,), bool)
        # the plain loop keeps one decode step in flight: a slot's pending
        # token is the host's (its prefill chose it) or the device's (the
        # step before chose it, read or not); ``_left`` counts the steps a
        # slot may still be dispatched into before its answer is full
        self._from_host = np.zeros((S,), bool)
        self._left = np.zeros((S,), np.int64)
        self._inflight = None        # the _Step dispatched and not yet read
        # the draft model's own block tables (its pool is auto-sized to
        # slots x max-context pages, so draft growth can never exhaust)
        self._draft_bt = np.zeros((S, MP), np.int32) \
            if self._draft is not None else None

        self._cond = threading.Condition()
        self._q = deque()
        self._stopped = False
        self._error = None
        self._decode_steps = 0       # decode/spec steps dispatched so far
        self._prefilled = False      # a prefill ran since the last decode step
        self._stream_s = 0.0         # seconds in stream_fn callbacks this turn
        self._step_hook = None       # test seam: called before each decode
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="generate-%s" % name)
        self._thread.start()

    # -- producer side -------------------------------------------------------
    def submit(self, tokens, max_new_tokens=None, eos_id=None,
               deadline=None, stream_fn=None, timeout=None):
        """Enqueue one generate request; returns a Future resolving to
        ``{"tokens": [int], "finish_reason": "eos"|"length",
        "ttft_s", "latency_s", "prompt_tokens", "rid"}``. ``deadline``
        (seconds from now) marks it sheddable at dequeue (PR 9) AND
        bounds the decode run itself — a mid-generation expiry fails
        the future with :class:`DeadlineExceeded` and recycles the
        slot + pages. ``max_new_tokens`` is capped by
        ``MXNET_GENERATE_MAX_STEPS`` and the per-slot context bound."""
        with profiler.span("mx.serve.submit") as span:
            return self._submit(span, tokens, max_new_tokens, eos_id,
                                deadline, stream_fn, timeout)

    def _submit(self, span, tokens, max_new_tokens, eos_id, deadline,
                stream_fn, timeout):
        pred = self.predictor
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.shape[0] < 1:
            raise GenerateError("submit: empty prompt")
        if int(tokens.min()) < 0 or int(tokens.max()) >= pred.config.vocab:
            # the compiled programs CLAMP ids (shape-static gather);
            # serving a clamped id would silently diverge from the
            # zero-masking one-shot forward, so reject at the door
            raise GenerateError(
                "submit: prompt token ids must lie in [0, %d), got "
                "range [%d, %d]" % (pred.config.vocab, tokens.min(),
                                    tokens.max()))
        if tokens.shape[0] > pred.max_ctx - 1:
            raise GenerateError(
                "submit: %d-token prompt exceeds the per-slot context "
                "bound %d (need room for >= 1 generated token)"
                % (tokens.shape[0], pred.max_ctx))
        if pred.pages_needed(tokens.shape[0]) > pred.pool.num_pages:
            raise PagePoolExhausted(
                "submit: prompt needs %d pages, the whole pool holds %d"
                % (pred.pages_needed(tokens.shape[0]),
                   pred.pool.num_pages))
        max_new = self._max_steps if max_new_tokens is None \
            else int(max_new_tokens)
        if max_new < 1:
            raise GenerateError("submit: max_new_tokens must be >= 1, "
                                "got %d" % max_new)
        max_new = min(max_new, self._max_steps,
                      pred.max_ctx - int(tokens.shape[0]))
        if deadline is not None:
            deadline = float(deadline)
            if not deadline > 0:
                raise GenerateError("submit: deadline must be > 0 "
                                    "seconds, got %r" % deadline)
            deadline = time.monotonic() + deadline
        req = _GenRequest(tokens, max_new, eos_id, deadline, stream_fn)
        span.set_metadata(rid=req.rid, prompt_tokens=int(tokens.shape[0]))
        wait_until = time.monotonic() + (
            self._submit_timeout if timeout is None else float(timeout))
        with self._cond:
            while True:
                if self._stopped:
                    if self._error is not None:
                        raise ServingError("GenerateServer %r: worker "
                                           "died: %r" % (self.name,
                                                         self._error))
                    raise ServerClosed("GenerateServer %r is closed"
                                       % self.name)
                if len(self._q) < self._depth:
                    break
                remaining = wait_until - time.monotonic()
                if remaining <= 0:
                    raise ServerOverloaded(
                        "GenerateServer %r: request queue full (%d "
                        "queued, MXNET_SERVE_QUEUE_DEPTH=%d)"
                        % (self.name, len(self._q), self._depth))
                self._cond.wait(min(remaining, 0.1))
            self._q.append(req)
            depth = len(self._q)
            self._cond.notify_all()
        profiler.generate_record(requests=1, queue_depth=depth)
        return req.future

    def generate(self, tokens, **kw):
        """Synchronous convenience wrapper around :meth:`submit`."""
        return self.submit(tokens, **kw).result()

    # -- worker side ---------------------------------------------------------
    def _active_count(self):
        return int(self._active.sum())

    def _alloc_pages(self, n):
        """``pool.alloc`` with prefix-index pressure relief: under
        exhaustion, evict least-recently-matched index entries until
        the allocation fits or the index is empty — so sharing never
        causes a :class:`PagePoolExhausted` a no-sharing run would
        avoid. (An evicted page only becomes free once no live request
        still shares it, hence the loop.)"""
        pred = self.predictor
        while True:
            try:
                return pred.pool.alloc(n)
            except PagePoolExhausted:
                if self._prefix is None or \
                        not self._prefix.evict_lru(pred.pool):
                    raise
                profiler.generate_record(prefix_evictions=1)

    def _reserve_pages(self, r):
        """Reserve a request's KV pages at admission: match the longest
        cached prefix (those pages are shared copy-on-write — the match
        already took the request's reference on each) and allocate
        private pages for the remainder. On exhaustion the match
        references are released and the FULL allocation is retried
        unshared — sharing must never block an admission the unshared
        path could serve — before the exhaustion propagates."""
        pred = self.predictor
        need = pred.pages_needed(r.tokens.shape[0])
        matched = []
        if self._prefix is not None:
            matched = self._prefix.match([int(t) for t in r.tokens],
                                         pred.pool)
        try:
            tail = self._alloc_pages(need - len(matched))
        except PagePoolExhausted:
            if not matched:
                raise
            pred.pool.unref(matched)
            matched, tail = [], self._alloc_pages(need)
        r.pages = matched + tail
        r.shared = len(matched)
        r.prefix_len = len(matched) * pred.page_size

    def _admit_locked(self):
        """Pop admissible requests into slots (shedding expired ones at
        dequeue); pages are reserved here so a request is only popped
        when its prompt fits. Returns (admitted, shed, starved) —
        ``starved`` is a request that can NEVER be admitted (no active
        slots to recycle pages from and nothing else admitted this
        round): it must fail typed instead of stalling forever."""
        pred = self.predictor
        admitted, shed = [], []
        if self._policy == "drain" and self._active_count() > 0:
            return admitted, shed, None
        free = [i for i in range(pred.slots) if self._slot_req[i] is None]
        now = time.monotonic()
        while free and self._q:
            r = self._q[0]
            if r.deadline is not None and now > r.deadline:
                shed.append(self._q.popleft())
                continue
            try:
                self._reserve_pages(r)
            except PagePoolExhausted:
                if not admitted and self._active_count() == 0:
                    return admitted, shed, self._q.popleft()
                break     # backpressure: completions will recycle pages
            self._q.popleft()
            r.slot = free.pop(0)
            r.queue_wait = time.perf_counter() - r.t_submit
            self._slot_req[r.slot] = r
            admitted.append(r)
        if shed or admitted:
            self._cond.notify_all()   # queue space freed
        return admitted, shed, None

    def _record_pool(self):
        s = self.predictor.pool.stats()
        profiler.generate_record(pages_in_use=s["in_use"],
                                 pages_high_water=s["high_water"],
                                 pool_pages=s["num_pages"],
                                 page_ref_high_water=s["ref_high_water"])
        if self._prefix is not None:
            profiler.generate_record(prefix_pages=self._prefix.pages)

    def _vacate(self, r):
        slot = r.slot
        with self._cond:
            self._slot_req[slot] = None
            self._active[slot] = False
            self._block_tables[slot, :] = 0
            self._positions[slot] = 0
            self._tokens[slot] = 0
            self._from_host[slot] = False
            self._left[slot] = 0
            if self._draft_bt is not None:
                self._draft_bt[slot, :] = 0
            self._cond.notify_all()
        if r.pages:
            # drops ONE reference per page: private pages free, shared
            # prefix pages just decrement (the index and/or other
            # requests still hold theirs)
            self.predictor.pool.free(r.pages)
            r.pages = []
        if r.draft_pages:
            self._draft.pool.free(r.draft_pages)
            r.draft_pages = []
        self._record_pool()

    def _flush_stream(self, r, final=False):
        if r.stream_fn is None:
            r.unflushed = []
            return
        if r.unflushed and (final or len(r.unflushed) >= self._flush_every):
            chunk, r.unflushed = r.unflushed, []
            t0 = time.perf_counter()
            try:
                r.stream_fn(chunk)
            except Exception:
                pass     # a broken stream consumer must not kill the loop
            self._stream_s += time.perf_counter() - t0

    def _finish(self, r, reason):
        with profiler.span("mx.serve.finish", rid=r.rid, reason=reason,
                           tokens=len(r.out)):
            self._vacate(r)
            self._flush_stream(r, final=True)
            profiler.generate_record(finished=1, **{reason: 1})
            r.future.set_result({
                "tokens": list(r.out),
                "finish_reason": reason,
                "prompt_tokens": int(r.tokens.shape[0]),
                "ttft_s": r.ttft,
                "latency_s": time.perf_counter() - r.t_submit,
                "rid": r.rid,
            })

    def _fail(self, r, exc, counter=None):
        counter = counter or "errors"
        with profiler.span("mx.serve.finish", rid=r.rid, reason=counter,
                           tokens=len(r.out)):
            self._vacate(r)
            self._flush_stream(r, final=True)
            profiler.generate_record(finished=1, **{counter: 1})
            if not r.future.done():
                r.future.set_exception(exc)

    def _check_done(self, r, tok):
        """EOS / length / deadline disposition for a just-produced
        token; returns True when the request left its slot."""
        if (r.eos_id is not None and tok == r.eos_id and not r.no_eos):
            self._finish(r, self.FINISH_EOS)
            return True
        if len(r.out) >= r.max_new:
            self._finish(r, self.FINISH_LENGTH)
            return True
        if r.deadline is not None and time.monotonic() > r.deadline:
            self._fail(r, DeadlineExceeded(
                "generate: deadline expired after %d token(s); slot and "
                "pages recycled" % len(r.out)), counter="deadline")
            return True
        return False

    def _prefill_one(self, r):
        n_prompt = int(r.tokens.shape[0])
        with profiler.span("mx.serve.prefill", rid=r.rid, slot=r.slot,
                           prompt_tokens=n_prompt,
                           bucket=self.predictor.pick_bucket(
                               n_prompt - r.prefix_len),
                           prefix_len=r.prefix_len,
                           queue_wait_ms=round(r.queue_wait * 1e3, 3),
                           active=self._active_count()):
            self._prefill(r, n_prompt)

    def _prefill(self, r, n_prompt):
        pred = self.predictor
        if chaos.generate_fault() == "stall":
            r.no_eos = True    # the request that never emits EOS
        t0 = time.perf_counter()
        try:
            with profiler.span("mx.serve.prefill.device"):
                if r.prefix_len:
                    # shared-prefix admission: the first prefix_len tokens'
                    # K/V already live in the matched (shared) pages — run
                    # only the uncovered tail, which attends the shared
                    # pages but writes exclusively the private ones (COW)
                    logits = pred.extend_tail(r.tokens[r.prefix_len:],
                                              r.prefix_len, r.pages)
                else:
                    logits = pred.prefill(r.tokens, r.pages)
            if self._draft is not None:
                r.draft_pages = self._draft.pool.alloc(
                    self._draft.pages_needed(n_prompt))
                self._draft.prefill(r.tokens, r.draft_pages)
        except BaseException as e:
            self._fail(r, e)
            return
        now = time.perf_counter()
        self._prefilled = True
        r.ttft = now - r.t_submit
        tok = int(np.argmax(logits))
        r.out.append(tok)
        r.unflushed.append(tok)
        # tokens counts every GENERATED token; the first one comes out
        # of prefill, the rest out of decode steps. prefill_tokens
        # counts tokens actually RUN — a matched prefix's tokens land
        # in prefill_tokens_saved instead (their sum is the prompt)
        profiler.generate_record(prefills=1, tokens=1,
                                 prefill_tokens=n_prompt - r.prefix_len,
                                 prefill_seconds=now - t0,
                                 ttfts=[r.ttft])
        if r.prefix_len:
            profiler.generate_record(prefix_hits=1,
                                     shared_pages=r.shared,
                                     prefill_tokens_saved=r.prefix_len)
        if self._prefix is not None:
            # index this prompt's full pages for future admissions (the
            # index takes its own reference on newly indexed pages, so
            # they outlive this request)
            self._prefix.insert([int(t) for t in r.tokens], r.pages,
                                pred.pool)
        self._record_pool()
        slot = r.slot
        self._block_tables[slot, :len(r.pages)] = r.pages
        self._positions[slot] = n_prompt
        self._tokens[slot] = tok
        self._from_host[slot] = True
        self._left[slot] = r.max_new - 1
        if self._draft is not None:
            self._draft_bt[slot, :len(r.draft_pages)] = r.draft_pages
            r.draft_pos = n_prompt
        self._flush_stream(r)
        if not self._check_done(r, tok):
            self._active[slot] = True

    def _grow_pages(self, headroom=0):
        with profiler.span("mx.serve.grow_pages"):
            self._grow(headroom)

    def _grow(self, headroom):
        """Before a decode step is dispatched, make sure every slot it
        runs for owns the page(s) its next write positions land in — up
        to ``headroom`` extra positions past the pending one for a
        speculative round's verify writes; a pool that cannot grow a
        mid-flight request fails it typed (never a silent stall)."""
        pred = self.predictor
        for slot in self._next_slots():
            r = self._slot_req[slot]
            upto = min(int(self._positions[slot]) + headroom,
                       pred.max_ctx - 1)
            try:
                # a slot's table is filled from entry 0 in the order of its
                # page list, so the first entry to look at is the list's
                # length: a long context is not walked again every step
                for pidx in range(len(r.pages), upto // pred.page_size + 1):
                    page, = self._alloc_pages(1)
                    r.pages.append(page)
                    self._block_tables[slot, pidx] = page
                if self._draft is not None:
                    for pidx in range(len(r.draft_pages),
                                      upto // pred.page_size + 1):
                        page, = self._draft.pool.alloc(1)
                        r.draft_pages.append(page)
                        self._draft_bt[slot, pidx] = page
            except PagePoolExhausted as e:
                self._fail(r, PagePoolExhausted(
                    "generate: pool exhausted growing a mid-flight "
                    "request past %d token(s): %s" % (len(r.out), e)),
                    counter="exhausted")
                continue

    def _step_counts(self, active, tokens, seconds, **more):
        """One decode or speculative step's counters, in one record."""
        profiler.generate_record(
            decode_steps=1, tokens=tokens, slot_steps=self.predictor.slots,
            active_slot_steps=active, decode_seconds=seconds, **more)

    def _next_slots(self):
        """The slots the next decode step runs for: active, and short of a
        full answer by more than the token a step in flight will bring."""
        return np.flatnonzero(self._active & (self._left > 0))

    def _dispatch(self, slots, ahead):
        """Dispatch one decode step for ``slots`` and read nothing: each
        takes its prefill's token from the host or the id the step before
        chose on the device, and advances by one position."""
        pred = self.predictor
        running = np.zeros((pred.slots,), bool)
        running[slots] = True
        chosen = pred.decode_ahead(self._tokens, self._from_host,
                                   self._positions, self._block_tables,
                                   running)
        self._from_host[slots] = False
        self._positions[slots] += 1
        self._left[slots] -= 1
        counts = dict(
            decode_steps_ahead=int(ahead),
            decode_steps_after_prefill=int(self._prefilled),
            decode_kv_pages_read=int(np.sum(
                -(-self._positions[slots] // pred.page_size))),
            decode_kv_pages_spanned=pred.slots * pred.max_pages_per_slot)
        self._prefilled = False
        self._decode_steps += 1
        return _Step(chosen, slots, [self._slot_req[s] for s in slots],
                     counts, self._decode_steps - 1)

    def _decode_step(self, dispatch=True):
        """One turn of the plain decode loop, which keeps a step in flight:
        dispatch the next step for the slots that want one (none with
        ``dispatch`` false), then read the ids the step before chose, append,
        stream, and check ``eos`` / length / deadline.  A length finish is
        foreseen (``_left``) and its slot left out of the step ahead; an
        ``eos`` or a deadline is seen once the next step is dispatched, and
        that step's id for the slot is dropped when its turn to be read
        comes: its one cache row went to a page the request still owned,
        and whatever reuses the page is dispatched after it."""
        pred = self.predictor
        if self._step_hook is not None:
            self._step_hook()
        slots = self._next_slots() if dispatch else ()
        before, self._inflight = self._inflight, None
        t0 = time.perf_counter()
        with profiler.span("mx.serve.decode.device"):
            if len(slots):
                with profiler.span("mx.serve.decode.dispatch",
                                   step=self._decode_steps, active=len(slots)):
                    self._inflight = self._dispatch(slots, before is not None)
            if before is not None:
                with profiler.span("mx.serve.decode.read", step=before.step):
                    ids, counters = pred.read_step(before.chosen)
        seconds = time.perf_counter() - t0
        if before is None:
            profiler.generate_record(decode_seconds=seconds)
            return
        kept = 0
        with profiler.span("mx.serve.decode.sample"):
            for slot, r in zip(before.slots, before.reqs):
                if self._slot_req[slot] is not r:
                    continue     # it left on the token before this one
                kept += 1
                tok = int(ids[slot])
                r.out.append(tok)
                r.unflushed.append(tok)
                self._flush_stream(r)
                self._check_done(r, tok)
        self._step_counts(
            kept, kept, seconds,
            decode_tokens_discarded=len(before.slots) - kept,
            **before.counts, **counters)

    def _spec_step(self):
        """One speculative-decoding round (ISSUE 16), replacing one
        single-token decode step when ``spec_k > 0``:

        1. the DRAFT predictor catches its KV cache up to each slot's
           committed chain, then autoregressively proposes up to k
           tokens per slot (batched single-token draft steps with
           per-slot feed cursors — slots needing fewer sub-steps go
           inactive early);
        2. ONE batched ``extend`` of the TARGET verifies, per slot, the
           pending token plus the k proposals (k+1 rows, one program);
        3. the longest proposal prefix agreeing with the target's
           argmax chain is accepted and emitted, plus the target's own
           next token (the replacement on first disagreement, the bonus
           token on full acceptance).

        Every emitted token IS the argmax of the target's logits given
        the tokens before it — acceptance is argmax equality — so the
        emitted chain is token-for-token the non-speculative greedy
        chain, and EOS / length / deadline disposition runs per emitted
        token in order (truncation parity). Rejected proposals leave
        K/V garbage at positions past the accepted prefix in both
        caches; the next round's writes land there before any query
        attends them (the padded-prefill-tail invariant)."""
        if self._step_hook is not None:
            self._step_hook()
        active = [int(s) for s in np.flatnonzero(self._active)]
        if not active:
            return
        t0 = time.perf_counter()
        with profiler.span("mx.serve.decode.device", step=self._decode_steps,
                           active=len(active)):
            chain_len, k_i, props, logits = self._spec_propose_verify(active)
        seconds = time.perf_counter() - t0
        with profiler.span("mx.serve.decode.sample"):
            emitted = self._spec_accept(active, chain_len, k_i, props, logits)
        self._step_counts(len(active), emitted, seconds, spec_rounds=1,
                          decode_steps_after_prefill=int(self._prefilled))
        self._prefilled = False
        self._decode_steps += 1

    def _spec_propose_verify(self, active):
        """Draft and verify phases of one round: per-slot chain lengths,
        proposal budgets, proposals, and the target's logits for them."""
        pred, draft, k = self.predictor, self._draft, self._spec_k
        S = pred.slots

        chain_len, k_i, feed, props = {}, {}, {}, {}
        for s in active:
            r = self._slot_req[s]
            chain = [int(t) for t in r.tokens] + r.out
            L = len(chain)                    # pending sits at L - 1
            chain_len[s] = L
            k_i[s] = max(0, min(k, pred.max_ctx - L))
            # tokens the draft cache hasn't ingested yet (committed
            # chain only; proposals are appended as they are drafted)
            feed[s] = [(chain[p], p) for p in range(r.draft_pos, L)]
            props[s] = []

        # -- draft phase: batched single-token steps ------------------
        while True:
            todo = [s for s in active if len(props[s]) < k_i[s]]
            if not todo:
                break
            toks = np.zeros((S,), np.int32)
            poss = np.zeros((S,), np.int32)
            act = np.zeros((S,), bool)
            fed = {}
            for s in todo:
                if feed[s]:
                    t, p = feed[s].pop(0)
                else:
                    j = len(props[s])
                    t, p = props[s][j - 1], chain_len[s] + j - 1
                toks[s], poss[s], act[s] = t, p, True
                fed[s] = p
            logits = draft.decode(toks, poss, self._draft_bt, act)
            for s in todo:
                # feeding position p yields the draft's prediction for
                # p + 1; only positions at/past the chain end propose
                if fed[s] >= chain_len[s] - 1:
                    props[s].append(int(np.argmax(logits[s])))
                r = self._slot_req[s]
                r.draft_pos = max(r.draft_pos, fed[s] + 1)

        # -- verify phase: one batched target extend ------------------
        T = k + 1
        vt = np.zeros((S, T), np.int32)
        vp = np.zeros((S, T), np.int32)
        vv = np.zeros((S, T), bool)
        for s in active:
            n = 1 + k_i[s]
            vt[s, :n] = [vtok for vtok in
                         ([self._tokens[s]] + props[s])[:n]]
            vp[s, :n] = np.arange(chain_len[s] - 1,
                                  chain_len[s] - 1 + n)
            vv[s, :n] = True
        logits = pred.extend(vt, vp, self._block_tables, vv)
        return chain_len, k_i, props, logits

    def _spec_accept(self, active, chain_len, k_i, props, logits):
        """Accept phase: emit the agreed prefix and the target's own next
        token per slot; returns the tokens emitted."""
        emitted_total = 0
        for s in active:
            r = self._slot_req[s]
            L, ks = chain_len[s], k_i[s]
            accepted, emit = 0, []
            for j in range(ks + 1):
                t_target = int(np.argmax(logits[s, j]))
                emit.append(t_target)
                if j < ks and props[s][j] == t_target:
                    accepted += 1
                    continue
                break
            # draft cache is correct up to position L + accepted - 1
            # (chain[L-1] + the accepted proposals); anything it wrote
            # past that is a rejected token's K/V — rewind the cursor
            # so the next round overwrites it
            r.draft_pos = min(r.draft_pos, L + accepted)
            profiler.generate_record(draft_proposed=ks,
                                     draft_accepted=accepted)
            done = False
            for t in emit:
                r.out.append(t)
                r.unflushed.append(t)
                emitted_total += 1
                self._tokens[s] = t
                self._flush_stream(r)
                if self._check_done(r, t):
                    done = True
                    break
            if not done:
                self._positions[s] = L - 1 + len(emit)
        return emitted_total

    def _wait_for_work(self):
        """Block until there is something to do; False once stopped.  Waits
        in slices, each a span, so a trace that starts or stops inside the
        wait loses at most one."""
        with self._cond:
            while (not self._q and not self._active_count()
                   and self._inflight is None and not self._stopped):
                with profiler.span("mx.serve.wait_work"):
                    self._cond.wait(_WAIT_SLICE_S)
            return not self._stopped

    def _turn(self):
        """One turn of the loop: admit, prefill what was admitted (after
        reading the step in flight, if any), one decode step: the next one
        dispatched, the one before read and streamed. False once stopped."""
        with profiler.span("mx.serve.admit") as admit:
            with self._cond:
                if self._stopped:
                    return False
                admitted, shed, starved = self._admit_locked()
            admit.set_metadata(admitted=len(admitted))
            if admitted:
                profiler.generate_record(
                    queue_waits=[r.queue_wait for r in admitted])
            if shed:
                exc = DeadlineExceeded(
                    "generate: deadline expired before admission "
                    "(shed at dequeue)")
                for r in shed:
                    if not r.future.done():
                        r.future.set_exception(exc)
                profiler.generate_record(shed=len(shed))
            if starved is not None:
                self._fail(starved, PagePoolExhausted(
                    "generate: prompt of %d token(s) cannot be "
                    "admitted — pool empty with no requests in "
                    "flight to recycle from"
                    % starved.tokens.shape[0]), counter="exhausted")
        if admitted and self._inflight is not None:
            # what is in flight is read and streamed before the loop waits
            # for a prefill's logits
            self._decode_step(dispatch=False)
        for r in admitted:
            self._prefill_one(r)
        if not self._active_count() and self._inflight is None:
            return True
        if self._draft is not None:
            # speculative round: verify writes up to spec_k
            # positions past the pending token
            self._grow_pages(headroom=self._spec_k)
            if self._active_count():
                self._spec_step()
        else:
            self._grow_pages()
            if self._active_count() or self._inflight is not None:
                self._decode_step()
        return True

    def _run(self):
        try:
            while self._wait_for_work():
                t0 = time.perf_counter()
                with profiler.span("mx.serve.loop",
                                   active=self._active_count(),
                                   queued=len(self._q)):
                    alive = self._turn()
                profiler.generate_record(
                    loop_seconds=time.perf_counter() - t0,
                    stream_seconds=self._stream_s)
                self._stream_s = 0.0
                if not alive:
                    return
        except BaseException as e:   # loop death: sticky, fail everything
            self._inflight = None
            with self._cond:
                self._error = e
                self._stopped = True
                pending = list(self._q)
                self._q.clear()
                inflight = [r for r in self._slot_req if r is not None]
                self._cond.notify_all()
            for r in pending:
                if not r.future.done():
                    r.future.set_exception(e)
            for r in inflight:
                self._fail(r, e)

    # -- observability / lifecycle -------------------------------------------
    def stats(self, reset=False):
        """Generative-serving counters (see profiler.generate_stats)."""
        return profiler.generate_stats(reset=reset)

    @property
    def prefix(self):
        """The :class:`~.generate.PrefixIndex` (None when sharing is
        off)."""
        return self._prefix

    @property
    def draft_predictor(self):
        """The draft :class:`~.generate.GenerativePredictor` (None when
        speculative decoding is off)."""
        return self._draft

    def prefix_stats(self):
        """Prefix-index counters, or None when sharing is off."""
        return None if self._prefix is None else self._prefix.stats()

    def clear_prefix(self):
        """Evict every prefix-index entry, releasing the index's page
        references — after the last in-flight request finishes the pool
        then drains to ``in_use == 0`` (the leak-check hook)."""
        if self._prefix is not None:
            self._prefix.clear(self.predictor.pool)
            self._record_pool()

    @property
    def admit_policy(self):
        return self._policy

    def pending(self):
        with self._cond:
            return len(self._q) + sum(1 for r in self._slot_req
                                      if r is not None)

    def close(self, timeout=5.0):
        """Stop the decode loop, fail queued AND in-flight requests
        with the typed :class:`ServerClosed` (a router may retry them
        elsewhere), recycle every page. Idempotent."""
        with self._cond:
            if self._stopped and self._error is None and \
                    not any(self._slot_req) and not self._q:
                return
            self._stopped = True
            self._cond.notify_all()
        self._thread.join(timeout)
        exc = ServerClosed("GenerateServer closed before the request "
                           "finished")
        with self._cond:
            pending = list(self._q)
            self._q.clear()
            inflight = [r for r in self._slot_req if r is not None]
        for r in pending:
            if not r.future.done():
                r.future.set_exception(exc)
        for r in inflight:
            self._fail(r, exc)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
