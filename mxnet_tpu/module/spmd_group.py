"""Fused SPMD execution group for Module(kvstore='tpu').

The reference's ``kvstore='device'/'nccl'`` tier runs one executor per GPU
and reduces gradients through a Comm tree (module/module.py:468-530 +
kvstore comm.h). The TPU-native tier replaces that whole pipeline with ONE
compiled XLA program per batch: forward + backward + optimizer update with
the batch sharded over the mesh's ``dp`` axis, so the gradient all-reduce
is a psum over ICI *inside* the step (the reference's priority-scheduled
push/pull overlap becomes XLA latency hiding).

Module routes ``forward_backward``/``update`` here when it detects a
``tpu`` kvstore; the kvstore itself carries the mesh (TPUKVStore.mesh) for
introspection parity.
"""
from __future__ import annotations

import collections
import pickle
import time

import numpy as np

from .. import config, profiler
from ..base import MXNetError
from ..ndarray import ndarray as nd
from ..parallel.feed import is_preplaced, place_batch_array
from ..parallel.spmd import (
    TrainStep,
    data_sharding,
    functional_from_optimizer,
)


class _DeviceMetricSource:
    """Device-resident (sum, count) accumulator attached to an EvalMetric
    by :meth:`FusedSPMDGroup.update_metric`. ``add`` folds one batch's
    in-step statistics with an async jitted device add (jit, not eager:
    the stats are replicated over the group's GLOBAL mesh, and eager ops
    on non-fully-addressable arrays are rejected on multi-host — jit is
    the supported multiprocess path); ``drain`` is the ONE blocking
    ``jax.device_get`` (legal on fully-replicated arrays), run by
    ``EvalMetric.get()`` at Speedometer/epoch boundaries."""

    def __init__(self, group, kind):
        self.group = group
        self.kind = kind  # stats key: "correct" | "sum_ce" | "sum_loss"
        self._sum = None
        self._n = None

    def add(self, stats):
        s, n = stats[self.kind], stats["n"]
        if self._sum is None:
            self._sum, self._n = s, n
        else:
            self._sum, self._n = self.group._metric_accumulate(
                (self._sum, self._n), (s, n))

    def drain(self):
        if self._sum is None:
            return 0.0, 0
        import jax

        s, n = jax.device_get((self._sum, self._n))
        self._sum = None
        self._n = None
        return float(s), int(n)

    def clear(self):
        self._sum = None
        self._n = None


class FusedSPMDGroup:
    """One fused train step over a dp mesh built from Module's contexts.

    With ``distributed=True`` (multi-process job via tools/launch.py /
    jax.distributed), the mesh is the GLOBAL ``("dcn", "dp")`` mesh from
    :func:`mxnet_tpu.dist.global_mesh`: every process contributes its
    local batch shard and the cross-host gradient all-reduce happens
    *inside* the compiled step over the dcn axis — XLA overlaps it with
    backprop (the reference got overlap from priority-scheduled push,
    model.py:126-137; the DistKVStore tier remains as the compatibility
    path when the fused step can't be used).
    """

    def __init__(self, symbol, contexts, optimizer, arg_params, aux_params,
                 data_names, label_names, fixed_param_names=None, logger=None,
                 batch_size=None, inputs_need_grad=False, distributed=False,
                 zero=None, compute_dtype=None):
        import jax

        if fixed_param_names:
            raise MXNetError("fused SPMD step: fixed_param_names not supported")
        if inputs_need_grad:
            raise MXNetError("fused SPMD step: inputs_need_grad not supported")
        devices = [c.jax_device() for c in contexts]
        if len({id(d) for d in devices}) != len(devices):
            raise MXNetError("fused SPMD step: duplicate devices in context list")
        if batch_size is not None and batch_size % len(devices) != 0:
            raise MXNetError(
                "fused SPMD step: batch size %d not divisible by %d devices"
                % (batch_size, len(devices)))
        self.distributed = bool(distributed)
        # ISSUE 20: tensor parallelism — the strictly-validated knobs
        # split the contexts into a (dp, mp) mesh and hand the parsed
        # MXNET_MP_RULES to TrainStep's param_shardings. mp=1 (the
        # default) builds the identical 1-axis {"dp": N} mesh as before
        # — bit-identical to the pure data-parallel path.
        from ..parallel.mesh import mp_size, train_mesh
        from ..parallel.spmd import parse_rules

        mp = mp_size()
        self._param_rules = parse_rules(config.get("MXNET_MP_RULES"))
        if self.distributed:
            from .. import dist

            self._dist = dist
            if mp > 1:
                raise MXNetError(
                    "fused dist step: MXNET_MP_SIZE=%d is single-process "
                    "only for now (the multi-host (dcn, dp, mp) mesh is "
                    "the scripted on-chip follow-up — see ROADMAP)" % mp)
            if len(devices) != jax.local_device_count():
                raise MXNetError(
                    "fused dist step: contexts must cover all %d local "
                    "devices (got %d)"
                    % (jax.local_device_count(), len(devices)))
            self.mesh = dist.global_mesh({"dp": len(devices)})
            data_axes = self.mesh.axis_names  # ("dcn","dp") when multi-proc
        else:
            self._dist = None
            self.mesh = train_mesh(devices=devices, mp=mp)
            data_axes = ("dp",)
        self._data_axes = tuple(data_axes)
        if mp > 1 and batch_size is not None \
                and batch_size % (len(devices) // mp) != 0:
            raise MXNetError(
                "fused SPMD step: batch size %d not divisible by the "
                "dp size %d (MXNET_MP_SIZE=%d over %d devices)"
                % (batch_size, len(devices) // mp, mp, len(devices)))
        # ISSUE 5 knobs: bound on compiled steps dispatched ahead of the
        # device (donated carry makes >1 safe) and the in-step metric
        # statistics that keep the hot loop free of per-batch host syncs
        max_inflight = config.get_int("MXNET_TPU_MAX_INFLIGHT", 2)
        if max_inflight is None or max_inflight < 1:
            raise MXNetError(
                "MXNET_TPU_MAX_INFLIGHT must be an integer >= 1 (got %r)"
                % config.get("MXNET_TPU_MAX_INFLIGHT"))
        self._max_inflight = max_inflight
        self._inflight = collections.deque()
        self._device_metrics = config.get_bool("MXNET_TPU_DEVICE_METRICS",
                                               True)
        # ISSUE 7: weight-update sharding — explicit arg wins, else the
        # (strictly validated) MXNET_TPU_ZERO knob, so Module.fit users
        # opt in via env or ctor without touching jax
        if zero is None:
            zero = config.get_strict_bool("MXNET_TPU_ZERO")
        self.zero = bool(zero)
        # Module(compute_dtype=): TrainStep's mixed precision — masters,
        # optimizer state and BN stats stay fp32
        self._compute_dtype = compute_dtype
        self._fopt = functional_from_optimizer(
            optimizer, [n for n in symbol.list_arguments()
                        if n not in data_names and n not in label_names])
        # rescale_grad already carries the 1/batch normalization Module set.
        self._ts = TrainStep(
            symbol, self._fopt, mesh=self.mesh, data_axes=self._data_axes,
            param_rules=self._param_rules,
            data_names=tuple(data_names), label_names=tuple(label_names),
            compute_dtype=self._compute_dtype,
            normalize_grads=False, return_outputs=True,
            metric_stats=self._device_metrics, zero=self.zero,
        )
        self.param_names = list(self._ts.param_names)
        self.aux_names = list(self._ts.aux_names)
        params = {k: arg_params[k]._data() for k in self.param_names}
        aux = {k: aux_params[k]._data() for k in self.aux_names}
        params, aux = self._sync_rank0(params, aux)
        opt_state = self._fopt.init(params)
        self._carry = self._ts.place(params, opt_state, aux)
        if mp > 1:
            # mpStats gauge (ISSUE 20): the measured per-chip footprint
            # of the freshly placed carry — the ~1/mp memory claim
            ms = self._ts.memory_stats(self._carry)
            profiler.mp_record(
                mp_size=mp, dp_size=len(devices) // mp,
                group_size=len(devices),
                param_bytes_per_chip=ms["param_bytes_per_dev"],
                live_bytes_per_chip=(ms["param_bytes_per_dev"]
                                     + ms["opt_bytes_per_dev"]
                                     + ms["aux_bytes_per_dev"]))
        self._data_names = list(data_names)
        self._label_names = list(label_names)
        self._output_names = list(symbol.list_outputs())
        self._key = jax.random.PRNGKey(0)
        self._step_no = 0
        self._loss = None
        self._outputs = None
        self._raw_outputs = None
        self._batch_sharding = data_sharding(self.mesh, self._data_axes)
        self._stats = None           # last step's in-program metric stats
        # per-metric double-accumulation guard: ids of the EvalMetric
        # objects that already folded the CURRENT batch's stats (a
        # batch-global flag would starve a second metric updated for
        # the same batch)
        self._stats_consumers = set()
        self._accum_fn = None        # jitted pairwise metric-stat add

    def _sync_rank0(self, params, aux):
        """Rank-0's host values win on every process (the reference's
        kvstore.init broadcast, kvstore_local.h) — one flattened
        collective for all params+aux. Arrays cross the wire as raw
        bytes (uint8) so every dtype — int64 counters, float64 — is
        bit-exact regardless of JAX's 32-bit canonicalization."""
        import jax

        if not self.distributed or jax.process_count() == 1:
            return params, aux
        keys_p = sorted(params)
        keys_a = sorted(aux)
        arrs = [np.ascontiguousarray(np.asarray(params[k])) for k in keys_p]
        arrs += [np.ascontiguousarray(np.asarray(aux[k])) for k in keys_a]
        if not arrs:
            return params, aux
        blob = np.frombuffer(b"".join(a.tobytes() for a in arrs), np.uint8)
        # the reduction promotes uint8 (sum dtype widening); every value
        # is still a byte (one nonzero contributor), so cast back
        buf = np.asarray(self._dist.broadcast0(blob),
                         np.uint8).tobytes()
        off = 0

        def take(a):
            nonlocal off
            v = np.frombuffer(buf, a.dtype, count=a.size,
                              offset=off).reshape(a.shape)
            off += a.nbytes
            return v

        out_p = {k: take(a) for k, a in zip(keys_p, arrs[:len(keys_p)])}
        out_a = {k: take(a) for k, a in zip(keys_a, arrs[len(keys_p):])}
        return out_p, out_a

    def _check_local_batch_agreement(self, n_rows_list):
        """A per-rank local-batch mismatch builds inconsistent global
        programs (a silent cross-host hang); turn it into an error.
        Runs unconditionally, ONE collective per batch covering every
        input array's leading dim: memoizing per-process would itself
        desynchronize ranks when one rank sees a repeat size while
        another sees a new one (unequal shard tails) — the exact
        deadlock this check exists to prevent."""
        # allgather the raw per-rank sizes and compare rows: exact for
        # any size < 2^31 (an allreduce of n^2 would wrap on the int32
        # wire — JAX canonicalizes int64 down — at n >= 46341)
        arr = np.asarray(n_rows_list, np.int32)
        rows = self._dist.allgather(arr)
        if not (rows == arr[None, :]).all():
            raise MXNetError(
                "fused dist step: local batch sizes %s differ across "
                "workers (per-rank sizes %s); pad or drop the tail "
                "batch so every rank agrees"
                % (list(n_rows_list), rows.tolist()))

    # -- the hot loop --------------------------------------------------------
    def forward_backward_update(self, data_batch):
        """Run one fused step: shard batch over the mesh data axes,
        fwd+bwd+update in XLA (cross-host all-reduce included).

        Batches already placed on the mesh (DeviceQueueIter) skip the
        device_put AND the per-batch cross-host agreement collective —
        a pre-placed global array fixed its global shape at
        construction. The step itself is dispatched asynchronously; the
        host throttles only when more than MXNET_TPU_MAX_INFLIGHT steps
        are outstanding (dispatch-ahead, ISSUE 5)."""
        import jax

        from .. import chaos

        # ISSUE 9 fault matrix: worker:R:nan@step=N poisons this step's
        # data batch — on the fused tier the gradient lives only inside
        # the compiled program, so the injection point is its input;
        # every gradient of the step goes non-finite, which is exactly
        # the class of silent fault the in-graph sentinel detects
        poison = chaos.nan_fault()
        arrays = list(zip(self._data_names, data_batch.data))
        labels = getattr(data_batch, "label", None) or []
        arrays += list(zip(self._label_names, labels))
        values = []
        host_rows = []
        for name, arr in arrays:
            value = arr._data() if isinstance(arr, nd.NDArray) else arr
            if poison and name == self._data_names[0]:
                value = value * np.float32("nan")
            if not is_preplaced(value, self._batch_sharding):
                host_rows.append(value.shape[0])
            values.append((name, value))
        if host_rows and self.distributed and jax.process_count() > 1:
            self._check_local_batch_agreement(host_rows)
        batch = {
            name: place_batch_array(self.mesh, self._data_axes,
                                    self.distributed, name, value,
                                    sharding=self._batch_sharding)
            for name, value in values
        }
        key = jax.random.fold_in(self._key, self._step_no)
        with profiler.span("mx.fit.dispatch", step=self._step_no):
            self._carry, result = self._ts(self._carry, batch, key)
        if self._device_metrics:
            loss, outs, self._stats = result
            self._stats_consumers.clear()
        else:
            loss, outs = result
        self._step_no += 1
        self._loss = loss
        # keep raw device arrays — materialization is deferred to
        # get_outputs() so the hot loop stays async when outputs
        # aren't consumed every step
        self._raw_outputs = outs
        self._outputs = None
        self._throttle(loss)

    def _throttle(self, token):
        """Dispatch-ahead bound: enqueue this step's completion token and
        block on the OLDEST one only when more than MXNET_TPU_MAX_INFLIGHT
        steps are outstanding — the host never runs unboundedly ahead of
        the device, but also never serializes on the step it just
        dispatched."""
        import jax

        self._inflight.append(token)
        while len(self._inflight) > self._max_inflight:
            t0 = time.perf_counter()
            with profiler.span("mx.fit.throttle"):
                jax.block_until_ready(self._inflight.popleft())
            profiler.h2d_record(
                stall_compute=time.perf_counter() - t0)
        profiler.h2d_record(steps=1, inflight=len(self._inflight))

    def drain(self):
        """Block until every dispatched step has retired. The explicit
        pipeline drain point: checkpoint/epoch/eval boundaries
        (copy_params_to, get_states) call it, and the PR 3 quiesce
        choreography inherits it through save_optimizer_states."""
        import jax

        while self._inflight:
            jax.block_until_ready(self._inflight.popleft())

    def _materialize_outputs(self, outs):
        """Wrap step outputs; in multi-process mode return each
        worker's own rows (the addressable shards of the global array),
        matching what this worker's metric expects to see."""
        import jax

        # a blocking device→host materialization: when this happens at
        # batch rate the loop is NOT stall-free — the profiler counter
        # is what the ISSUE 5 acceptance test asserts is zero on the
        # device-metric path
        profiler.h2d_record(host_syncs=1)
        with profiler.span("mx.fit.host_sync"):
            if not self.distributed or jax.process_count() == 1:
                return [nd.NDArray(o) for o in outs]
            return [nd.array(self._local_rows_host(o)) for o in outs]

    @staticmethod
    def _local_rows_host(o):
        """One global device array → this worker's own rows on host:
        fully-replicated arrays dedupe to shard 0; sharded arrays
        reassemble the addressable shards in row order."""
        if getattr(o, "is_fully_replicated", False):
            return np.asarray(o.addressable_data(0))
        # shards live on different local devices: assemble on host
        shards = sorted(
            o.addressable_shards,
            key=lambda s: (s.index[0].start or 0) if s.index else 0)
        seen = set()
        pieces = []
        for s in shards:
            k = tuple((sl.start, sl.stop) for sl in s.index)
            if k in seen:
                continue
            seen.add(k)
            pieces.append(np.asarray(s.data))
        return np.concatenate(pieces, axis=0)

    def _materialize_labels(self, labels):
        """Pre-placed (DeviceQueueIter) labels in multi-process jobs are
        global arrays whose remote shards ``jax.device_get`` cannot
        fetch; pull back this worker's own rows, mirroring
        :meth:`_materialize_outputs` for preds. Host arrays and
        single-process device labels pass through — the metric's
        batched ``device_get`` handles those."""
        import jax

        if not self.distributed or jax.process_count() == 1:
            return list(labels)
        out = []
        for l in labels:
            data = l._data() if isinstance(l, nd.NDArray) else l
            if (type(data).__module__.startswith("jax")
                    and not getattr(data, "is_fully_addressable", True)):
                l = nd.array(self._local_rows_host(data))
            out.append(l)
        return out

    def get_outputs(self):
        if self._outputs is None:
            if self._raw_outputs is None:
                raise MXNetError("fused SPMD step: no batch has run yet")
            self._outputs = self._materialize_outputs(self._raw_outputs)
        return list(self._outputs)

    def _device_metric_plan(self, eval_metric):
        """[(leaf_metric, stats_key)] when EVERY leaf of eval_metric can
        be reproduced exactly from the in-step statistics; None forces
        the host fallback (mixed accumulation would double-count)."""
        from .. import metric as metric_mod

        # the in-step stats cover outputs[0]/labels[0] only; a
        # multi-output/multi-label graph's host metric sums over EVERY
        # (label, pred) pair — force the host path rather than silently
        # reporting half the pairs
        if len(self._output_names) != 1 or len(self._label_names) != 1:
            return None
        stats = self._stats
        leaves, stack = [], [eval_metric]
        while stack:
            m = stack.pop()
            if isinstance(m, metric_mod.CompositeEvalMetric):
                stack.extend(m.metrics)
                continue
            leaves.append(m)
        plan = []
        for m in leaves:
            if m.output_names is not None or m.label_names is not None:
                return None  # name-filtered metrics need the real arrays
            if (type(m) is metric_mod.Accuracy and m.axis == 1
                    and "correct" in stats):
                plan.append((m, "correct"))
            elif (type(m) in (metric_mod.CrossEntropy,
                              metric_mod.NegativeLogLikelihood)
                    and m.eps == 1e-12 and "sum_ce" in stats):
                plan.append((m, "sum_ce"))
            else:
                return None
        return plan

    def _metric_accumulate(self, acc, batch_stats):
        """Jitted pairwise add of (sum, n) device scalars (async; the
        multiprocess-legal way to combine replicated global arrays)."""
        import jax

        if self._accum_fn is None:
            self._accum_fn = jax.jit(
                lambda a, b: jax.tree_util.tree_map(
                    lambda x, y: x + y, a, b))
        return self._accum_fn(acc, batch_stats)

    def _attach_source(self, m, kind):
        by_kind = m.__dict__.setdefault("_fused_metric_srcs", {})
        src = by_kind.get((id(self), kind))
        if src is None:
            src = by_kind[(id(self), kind)] = _DeviceMetricSource(self, kind)
        m._attach_device_source(src)
        return src

    def update_metric(self, eval_metric, labels):
        # Device-resident path (ISSUE 5): fold the step's in-program
        # statistics into device accumulators — eager async adds, zero
        # host syncs; EvalMetric.get() drains them at Speedometer/epoch
        # boundaries. In multi-process jobs the stats are GLOBAL sums
        # (they psum across hosts inside the compiled step), so every
        # worker's log shows the global metric.
        if self._device_metrics and self._stats is not None:
            plan = self._device_metric_plan(eval_metric)
            if plan is not None:
                if id(eval_metric) not in self._stats_consumers:
                    for m, kind in plan:
                        self._attach_source(m, kind).add(self._stats)
                    self._stats_consumers.add(id(eval_metric))
                return
        # Host fallback — same name-keyed dispatch as
        # DataParallelExecutorGroup.update_metric so metrics with
        # output_names/label_names pick the right arrays. Materializes
        # outputs: a per-batch host sync (profiler host_syncs counts it).
        from .. import metric as metric_mod

        t0 = time.perf_counter()
        labels_ = dict(zip(self._label_names,
                           self._materialize_labels(labels)))
        preds_ = dict(zip(self._output_names, self.get_outputs()))
        # the blocking read, timed here and not in the metric: the
        # executor-group path shares update_dict and is no part of the
        # pipeline counters (update_dict then finds host arrays)
        labels_, preds_ = metric_mod._materialize_dicts(labels_, preds_)
        profiler.h2d_record(sync_seconds=time.perf_counter() - t0)
        eval_metric.update_dict(labels_, preds_)

    # -- host sync -----------------------------------------------------------
    def _fetch_host(self, tree):
        """Device tree → host tree, legal on EVERY tier. A plain
        ``jax.device_get`` crashes on global arrays with non-addressable
        shards (the multi-process tier — same bug class as the PR 5
        label fallback): fully-replicated leaves dedupe to this
        process's shard 0, and genuinely sharded leaves (ZeRO optimizer
        state) all-gather through a jitted identity first (the
        multiprocess-legal collective), then read the local copy.
        Single-process trees keep the one batched device_get."""
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if all(getattr(l, "is_fully_addressable", True) for l in leaves):
            return jax.device_get(tree)
        rep = None
        out = []
        for l in leaves:
            if getattr(l, "is_fully_addressable", True):
                out.append(jax.device_get(l))
            elif getattr(l, "is_fully_replicated", False):
                out.append(np.asarray(l.addressable_data(0)))
            else:
                if rep is None:
                    from ..parallel.spmd import replicated

                    rep = jax.jit(
                        lambda x: x,
                        out_shardings=replicated(self.mesh))
                out.append(np.asarray(rep(l).addressable_data(0)))
        return jax.tree_util.tree_unflatten(treedef, out)

    def copy_params_to(self, arg_params, aux_params):
        self.drain()
        params, _opt, aux, _step = self._carry
        host_p, host_a = self._fetch_host((params, aux))  # one batched D2H
        for k in self.param_names:
            nd.NDArray(host_p[k]).copyto(arg_params[k])
        for k in self.aux_names:
            nd.NDArray(host_a[k]).copyto(aux_params[k])

    def _replace(self, params=None, opt_state=None, aux=None, step=None):
        """Re-place the carry, preserving the pieces not overridden."""
        import jax
        import jax.numpy as jnp
        from ..parallel.spmd import replicated

        self.drain()
        old_p, old_o, old_a, old_s = self._carry
        p = params if params is not None else dict(old_p)
        o = opt_state if opt_state is not None else old_o
        a = aux if aux is not None else dict(old_a)
        carry = self._ts.place(p, o, a)
        s = old_s if step is None else jax.device_put(
            jnp.asarray(step, jnp.int32), replicated(self.mesh))
        self._carry = (carry[0], carry[1], carry[2], s)

    def set_params(self, arg_params, aux_params):
        """Reset device params/aux from host (e.g. after load). In
        distributed mode rank-0's values win, same as __init__ — a
        per-process re-init must not silently desynchronize ranks."""
        params = {k: arg_params[k]._data() for k in self.param_names}
        aux = {k: aux_params[k]._data() for k in self.aux_names}
        params, aux = self._sync_rank0(params, aux)
        self._replace(params=params, aux=aux)

    # -- optimizer state -----------------------------------------------------
    _STATE_FORMAT = "fused-spmd-v1"

    def get_states(self):
        self.drain()
        params, opt_state, _aux, step_no = self._carry
        # ONE tree fetch instead of a blocking np.asarray per state
        # array (ISSUE 5 satellite), through the per-shard/allgather
        # path so ZeRO-sharded state on the multi-process tier never
        # hits device_get's non-addressable crash (ISSUE 7 satellite).
        # The blob stores the LOGICAL layout — un-padded, param-shaped,
        # mesh-size independent — so a state saved under zero=True on N
        # devices restores bit-exactly under zero=False (and any mesh).
        host = self._fetch_host(opt_state)
        logical = self._ts.logical_opt_state(host, params)
        return pickle.dumps({"format": self._STATE_FORMAT,
                             "opt_state": logical, "step": int(step_no),
                             "zero": self._ts.zero})

    def set_states(self, blob):
        try:
            data = pickle.loads(blob)
        except Exception as e:
            raise MXNetError("fused SPMD step: unreadable optimizer states "
                             "(%s)" % e)
        if not isinstance(data, dict) or data.get("format") != self._STATE_FORMAT:
            raise MXNetError(
                "fused SPMD step: optimizer-state file was not written by the "
                "fused (kvstore='tpu') path; resume with the same kvstore "
                "type it was saved under")
        self._replace(opt_state=data["opt_state"], step=data["step"])
        self._step_no = data["step"]

    # -- self-healing (ISSUE 9) ----------------------------------------------
    @property
    def sentinel(self):
        return self._ts.sentinel

    def health_stats(self):
        """Drain the in-graph sentinel's device counters (None when the
        sentinel is off). ONE blocking read of replicated scalars —
        the HealthGuard amortizes it over MXNET_TPU_GUARD_INTERVAL
        batches; the counters themselves accumulate inside the compiled
        step, so the steady-state loop stays sync-free. Publishes the
        snapshot to the profiler healthStats gauge."""
        snap = self._ts.health_stats(self._carry)
        if snap is not None:
            profiler.health_sentinel(snap)
        return snap

    def reset_optimizer(self, optimizer):
        """Rebuild the compiled step around the (re-tuned) imperative
        optimizer — the HealthGuard LR-backoff path. Params/aux stay
        device-resident; optimizer state round-trips through the
        logical layout into a fresh TrainStep (a recompile: rollback
        is exceptional, correctness beats a warm jit cache). Sentinel
        counters restart from zero — a rollback must not instantly
        re-trigger on the pre-rollback consec count."""
        import jax
        import jax.numpy as jnp
        from ..parallel.spmd import replicated

        self.drain()
        params, opt_state, aux, step_no = self._carry
        host_opt = self._fetch_host(opt_state)
        logical = self._ts.logical_opt_state(host_opt, params)
        self._fopt = functional_from_optimizer(
            optimizer, list(self.param_names))
        self._ts = TrainStep(
            self._ts.symbol, self._fopt, mesh=self.mesh,
            data_axes=self._data_axes,
            param_rules=self._param_rules,
            data_names=tuple(self._data_names),
            label_names=tuple(self._label_names),
            compute_dtype=self._compute_dtype,
            normalize_grads=False, return_outputs=True,
            metric_stats=self._device_metrics, zero=self.zero,
        )
        carry = self._ts.place(params, logical, aux)
        step = jax.device_put(
            jnp.asarray(int(self._fetch_host(step_no)), jnp.int32),
            replicated(self.mesh))
        self._carry = (carry[0], carry[1], carry[2], step)
