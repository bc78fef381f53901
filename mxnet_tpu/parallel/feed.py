"""Asynchronous host→device input pipeline.

Reference counterpart: the prefetch side of ``src/io/iter_prefetcher.h``
plus the pinned-memory staging the reference's GPU path got from
``cudaMemcpyAsync``. TPU-native design: the compiled fused step consumes
batches already sharded over the mesh (``NamedSharding`` over the data
axes), so the only host work left per batch is the ``jax.device_put`` —
and that transfer is exactly what :class:`DeviceQueueIter` moves off the
hot loop. A background thread converts/shards batch N+1 while step N
computes; the consumer pops finished device batches from a bounded queue
(depth ``MXNET_TPU_FEED_DEPTH``, default 2) so host memory stays bounded
and backpressure reaches the source iterator.

The placement function (:func:`place_batch_array`) is shared with
``FusedSPMDGroup`` so the pipelined path is bit-identical to the
synchronous one — single-chip ``device_put`` and multi-process
``make_array_from_process_local_data`` both included.

Where the bytes wait before that ``device_put`` is the worker's own
business: a host batch array bound for an accelerator is copied into a
reused staging buffer first (:class:`_StagingRing`), because the runtime
copies from host memory it has seen before several times faster than from
a fresh array every batch. No setting chooses it; the worker looks at
where the array and the mesh are (:meth:`DeviceQueueIter._new_ring`,
:func:`_host_rows`).
"""
from __future__ import annotations

import queue
import threading
import time
import warnings

import numpy as np

from .. import profiler
from ..base import MXNetError
from ..io import DataBatch, DataIter
from ..ndarray.ndarray import NDArray


def expected_sharding(mesh, data_axes):
    """The NamedSharding a batch array carries on this mesh's data axes —
    MUST stay bit-identical to the compiled step's input sharding, so it
    delegates to the one implementation (spmd.data_sharding): any
    divergence would silently defeat the is_preplaced fast path."""
    from .spmd import data_sharding

    return data_sharding(mesh, data_axes)


def is_preplaced(value, sharding):
    """True when ``value`` is already a device array laid out exactly as
    the compiled step expects (the DeviceQueueIter fast path)."""
    vs = getattr(value, "sharding", None)
    if vs is None:
        return False
    try:
        return vs.is_equivalent_to(sharding, value.ndim)
    except (TypeError, ValueError):
        return False


def place_batch_array(mesh, data_axes, distributed, name, value,
                      sharding=None):
    """Host batch array → device: local ``device_put``, or the
    process-local shard of the global batch in distributed mode. Records
    bytes/latency into the profiler's pipeline counters. ``value`` may be
    numpy or a single-device jax array; pre-placed arrays short-circuit.
    It copies from whatever ``value`` is and stages nothing itself: the
    ``DeviceQueueIter`` worker hands it a staging slot's buffer where it
    staged the batch, ``FusedSPMDGroup``'s unqueued call the batch as it
    came.
    """
    import jax

    sharding = sharding or expected_sharding(mesh, data_axes)
    if is_preplaced(value, sharding):
        profiler.h2d_record(preplaced=1)
        return value
    # size*itemsize, NOT np.asarray(value).nbytes: forcing a host
    # materialization just for byte accounting would re-add the very
    # per-batch copy this path exists to remove
    nbytes = int(value.size) * np.dtype(value.dtype).itemsize
    t0 = time.perf_counter()
    with profiler.span("mx.fit.h2d", name=name, nbytes=nbytes):
        if not distributed or jax.process_count() == 1:
            ndev = mesh.devices.size
            if value.shape[0] % ndev != 0:
                raise MXNetError(
                    "async feed: batch dim %d of %r not divisible by "
                    "%d mesh devices" % (value.shape[0], name, ndev))
            out = jax.device_put(value, sharding)
        else:
            local = np.asarray(value)
            nproc = jax.process_count()
            if local.shape[0] % jax.local_device_count() != 0:
                raise MXNetError(
                    "async feed: local batch dim %d of %r not divisible "
                    "by %d local devices"
                    % (local.shape[0], name, jax.local_device_count()))
            out = jax.make_array_from_process_local_data(
                sharding, local,
                global_shape=(local.shape[0] * nproc,) + local.shape[1:])
    profiler.h2d_record(nbytes=nbytes, puts=1,
                        seconds=time.perf_counter() - t0)
    return out


def _host_rows(arr):
    """``arr``'s contents as a numpy array over the memory they already
    lie in, or None when they are not on the host (a device array, placed
    or not, goes to :func:`place_batch_array` as it is). A numpy array is
    itself; a one-device array of the host backend is read in place; an
    ``NDArray`` view over such an array (``NDArrayIter``'s batches) is
    its slice of the root's memory, so no intermediate array is made."""
    value, index = arr._view_source() if isinstance(arr, NDArray) \
        else (arr, None)
    if index is not None and not isinstance(index, slice):
        value, index = arr._data(), None   # no numpy view of it: realize
    if not isinstance(value, np.ndarray):
        devices = getattr(value, "devices", None)
        if devices is None:
            return None
        devices = devices()
        if len(devices) != 1 or next(iter(devices)).platform != "cpu":
            return None
        value = np.asarray(value)          # zero-copy on the host backend
    return value if index is None else value[index]


class _Slot:
    __slots__ = ("buffer", "sent")

    def __init__(self):
        self.buffer = None  # host array, made at the first fill
        self.sent = None    # device array made from buffer's last contents


class _StagingRing:
    """Reused host buffers between a batch's rows and ``device_put``, owned
    by one ``DeviceQueueIter`` worker: two slots per ``(shape, dtype)``,
    each made when a batch array of that shape first needs it, so a tail
    batch of another shape never writes into a full batch's slot. The one
    rule: a slot is not written again before the device array made from
    its last contents is ready, because until then the runtime may still
    be reading the buffer. Two slots are what that rule needs to keep the
    worker moving: it fills one while the copy out of the other is in
    flight."""

    def __init__(self):
        self._slots = {}

    def fill(self, rows):
        """Copy ``rows`` into the next slot of their shape and return the
        slot; the caller sets ``slot.sent`` to the device array it makes
        from ``slot.buffer``. Time spent waiting for the slot's last
        transfer is counted (``stage_wait_seconds``)."""
        key = (rows.shape, rows.dtype)
        slots = self._slots.get(key)
        if slots is None:
            slots = self._slots[key] = [_Slot(), _Slot()]
        slot = slots[0]
        slots.reverse()
        if slot.buffer is None:
            slot.buffer = np.empty(rows.shape, rows.dtype)
        if slot.sent is not None:
            t0 = time.perf_counter()
            slot.sent.block_until_ready()
            profiler.h2d_record(stage_wait=time.perf_counter() - t0)
            slot.sent = None
        np.copyto(slot.buffer, rows)
        return slot


_END = object()    # inner iterator exhausted
_ABORT = object()  # worker thread died; see self._exc


class DeviceQueueIter(DataIter):
    """Wrap any :class:`DataIter` so batches arrive on the mesh already
    sharded, converted on a background thread while the previous step
    computes (ISSUE 5 tentpole). ``Module.fit`` on a fused kvstore puts
    one around the iterator it is given; wrap by hand only in a loop that
    calls ``forward_backward`` itself.

    A batch array is staged — copied into a reused host buffer of the
    worker's and sent from there — when it lies on the host (numpy, or an
    array or ``NDArray`` view of the host backend) and the mesh is on an
    accelerator. It goes as it came when it is already placed, when it
    lies on an accelerator, when the mesh is the host backend
    (``device_put`` may alias its source there, so a reused buffer would
    be unsafe and buys nothing) and in the multi-process branch. The
    bytes that reach the step are the same either way; no argument or
    environment variable chooses. ``profiler.pipeline_stats()`` counts
    ``staged`` beside ``puts`` and ``preplaced``.

    Parameters
    ----------
    data_iter : DataIter
        The host-side source iterator.
    group : FusedSPMDGroup, optional
        Take ``mesh``/``data_axes``/``distributed`` from a Module's fused
        group directly.
    module : Module, optional
        Bind lazily to ``module``'s fused group: resolution happens on
        the first ``next()``, which in ``Module.fit`` is after
        ``init_optimizer`` created the group — so the wrapper can be
        built BEFORE ``fit`` is called. When the module has no fused
        group (kvstore is not 'tpu'/'dist_*'), the iterator degrades to
        a transparent pass-through of host batches (with a warning).
    mesh, data_axes, distributed :
        Explicit placement spec when neither group nor module is given.
    depth : int
        Bounded pipeline depth (batches staged on device ahead of the
        consumer). Default ``MXNET_TPU_FEED_DEPTH`` (2).
    close_source : bool
        Whether :meth:`close` also closes ``data_iter``. Default True;
        ``Module.fit``, which wraps a CALLER-owned iterator itself, passes
        False so the caller can keep using it.

    Supports ``with DeviceQueueIter(...) as it:`` and explicit
    :meth:`close`; ``reset()`` restarts cleanly after ``StopIteration``
    or mid-epoch abandonment.
    """

    def __init__(self, data_iter, group=None, module=None, mesh=None,
                 data_axes=("dp",), distributed=False, depth=None,
                 close_source=True):
        super().__init__(getattr(data_iter, "batch_size", 0))
        from .. import config

        if depth is None:
            depth = config.get_int("MXNET_TPU_FEED_DEPTH", 2)
        depth = int(depth)
        if depth < 1:
            raise MXNetError(
                "DeviceQueueIter: depth must be >= 1 (got %d); set "
                "MXNET_TPU_FEED_DEPTH to a positive integer" % depth)
        self.data_iter = data_iter
        self.depth = depth
        self._close_source = bool(close_source)
        self._module = module
        self._passthrough = False
        self._group = None
        self.mesh = None
        self._checked_agreement = False
        self._local_rows = None   # constant-local-batch invariant (dist)
        self._closed = False
        self._thread = None
        self._q = None
        self._ring = None         # the running worker's _StagingRing
        self._exc = None
        self._stop = threading.Event()
        self._current_batch = None
        if group is not None or mesh is not None:
            self._bind(group=group, mesh=mesh, data_axes=data_axes,
                       distributed=distributed)
        elif module is None:
            raise MXNetError(
                "DeviceQueueIter: need a mesh (or group=/module=)")
        # module= defers binding to the first next()

    def _bind(self, group=None, mesh=None, data_axes=("dp",),
              distributed=False):
        if group is not None:
            mesh = group.mesh
            data_axes = group._data_axes
            distributed = group.distributed
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self.distributed = bool(distributed)
        self._sharding = expected_sharding(mesh, self.data_axes)
        self._group = group

    def _ensure_started(self):
        """Resolve deferred module binding and start the worker."""
        if self._thread is not None or self._passthrough:
            return
        if self.mesh is None:
            fused = getattr(self._module, "_fused", None)
            if fused is None:
                warnings.warn(
                    "DeviceQueueIter: module has no fused SPMD group "
                    "(kvstore != 'tpu'); passing host batches through "
                    "unchanged", stacklevel=3)
                self._passthrough = True
                return
            self._bind(group=fused)
        self._start()

    # -- pass-through metadata ----------------------------------------------
    @property
    def provide_data(self):
        return self.data_iter.provide_data

    @property
    def provide_label(self):
        return self.data_iter.provide_label

    # -- worker --------------------------------------------------------------
    def _new_ring(self):
        """A staging ring for the worker about to start, or None where
        every batch goes to ``device_put`` as it came: on a mesh of the
        host backend, and in the multi-process branch. It holds no
        buffer before the first host batch array."""
        import jax

        if self.mesh.devices.flat[0].platform == "cpu":
            return None
        if self.distributed and jax.process_count() > 1:
            return None
        return _StagingRing()

    def _start(self):
        self._q = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._ring = self._new_ring()
        self._exc = None
        # the worker binds THIS generation's queue/stop-event/ring as
        # locals: a reset() that times out joining a wedged worker
        # replaces them, and the abandoned thread must never be able to
        # inject a stale pre-reset batch into the new epoch's queue
        t = threading.Thread(target=self._worker,
                             args=(self._q, self._stop, self._ring),
                             name="DeviceQueueIter", daemon=True)
        self._thread = t
        t.start()

    @staticmethod
    def _put(q, stop, item):
        """Queue.put that stays responsive to close()/reset()."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _place_batch(self, batch, ring):
        rows = None

        def place(name, arr):
            nonlocal rows
            slot = None
            host = _host_rows(arr) if ring is not None else None
            if host is not None:
                with profiler.span("mx.fit.stage", name=name,
                                   nbytes=host.nbytes):
                    slot = ring.fill(host)
                value = slot.buffer
            else:
                value = arr._data() if isinstance(arr, NDArray) else arr
            if rows is None and not is_preplaced(value, self._sharding):
                rows = int(value.shape[0])
            placed = place_batch_array(
                self.mesh, self.data_axes, self.distributed, name, value,
                sharding=self._sharding)
            if slot is not None:
                slot.sent = placed
                profiler.h2d_record(staged=1)
            return NDArray(placed)

        names_d = [d[0] if isinstance(d, tuple) else d.name
                   for d in (self.provide_data or [])]
        names_l = [d[0] if isinstance(d, tuple) else d.name
                   for d in (self.provide_label or [])]
        data = [place(names_d[i] if i < len(names_d) else "data%d" % i, a)
                for i, a in enumerate(batch.data or [])]
        label = [place(names_l[i] if i < len(names_l) else "label%d" % i, a)
                 for i, a in enumerate(batch.label or [])]
        if self.distributed and rows is not None:
            if self._local_rows is None:
                self._local_rows = rows
            elif rows != self._local_rows:
                raise MXNetError(
                    "DeviceQueueIter: local batch size changed mid-stream "
                    "(%d -> %d); pad or discard the tail batch so every "
                    "rank keeps a constant shape" % (self._local_rows, rows))
        out = DataBatch(data, label or None, pad=batch.pad,
                        index=batch.index,
                        provide_data=batch.provide_data,
                        provide_label=batch.provide_label)
        return out

    def _worker(self, q, stop, ring):
        try:
            while not stop.is_set():
                try:
                    batch = self.data_iter.next()
                except StopIteration:
                    self._put(q, stop, _END)
                    return
                placed = self._place_batch(batch, ring)
                profiler.h2d_record(batches=1, queue_depth=q.qsize())
                if not self._put(q, stop, placed):
                    return
        except BaseException as e:  # surfaced on the consumer thread
            self._exc = e
            self._put(q, stop, _ABORT)

    # -- consumer ------------------------------------------------------------
    def next(self):
        if self._closed:
            raise MXNetError("DeviceQueueIter: iterator is closed")
        self._ensure_started()
        if self._passthrough:
            return self.data_iter.next()
        t0 = time.perf_counter()
        with profiler.span("mx.fit.feed_wait"):
            item = self._q.get()
        profiler.h2d_record(stall_feed=time.perf_counter() - t0)
        if item is _END:
            # leave a sentinel for repeated next() calls post-epoch
            self._q.put(_END)
            raise StopIteration
        if item is _ABORT:
            self._q.put(_ABORT)  # repeated next() keeps raising
            if self._exc is not None:
                raise self._exc
            raise StopIteration
        if self.distributed and not self._checked_agreement:
            # ONE main-thread collective on the first batch: every rank
            # must agree on its local rows before compiled steps with
            # cross-host collectives start (a mismatch builds
            # inconsistent global programs — a silent hang). Collectives
            # must never run on the worker thread: they would interleave
            # with the step's own collectives in arbitrary order. After
            # this, the pipeline relies on the constant-local-batch
            # invariant (_place_batch raises on a mid-stream change):
            # sources feeding a dist job MUST pad or discard tail
            # batches, because a rank that raises here cannot stop its
            # peers' already-dispatched collectives.
            import jax

            if jax.process_count() > 1 and self._local_rows is not None:
                if self._group is not None:
                    self._group._check_local_batch_agreement(
                        [self._local_rows])
                else:
                    from .. import dist

                    mine = np.asarray([self._local_rows], np.int32)
                    rows = dist.allgather(mine)
                    if not (rows == mine[None, :]).all():
                        raise MXNetError(
                            "DeviceQueueIter: local batch size %d differs "
                            "across workers (per-rank sizes %s); pad or "
                            "discard the tail batch so every rank agrees"
                            % (self._local_rows, rows.reshape(-1).tolist()))
            self._checked_agreement = True
        self._current_batch = item
        return item

    def iter_next(self):
        try:
            self.next()
            return True
        except StopIteration:
            return False

    def getdata(self):
        return self._current_batch.data

    def getlabel(self):
        return self._current_batch.label

    def getindex(self):
        return self._current_batch.index

    def getpad(self):
        return self._current_batch.pad

    # -- lifecycle -----------------------------------------------------------
    def _shutdown(self, timeout=5.0):
        self._stop.set()
        t = self._thread
        if t is not None and t.is_alive():
            # drain so a worker blocked in put() can observe the stop
            # flag; bounded — a worker wedged inside the SOURCE
            # iterator's next() is a daemon thread and may be abandoned
            deadline = time.monotonic() + timeout
            while t.is_alive() and time.monotonic() < deadline:
                try:
                    self._q.get_nowait()
                except queue.Empty:
                    pass
                t.join(timeout=0.05)
        self._thread = None
        self._ring = None  # the staging buffers go with the worker

    def reset(self):
        """Stop the worker, drop its staging buffers and reset the source
        iterator — valid after StopIteration AND after abandoning an
        epoch mid-stream. The worker starts again at the next ``next()``,
        as it first did: a reset nobody reads after (``fit``'s last) pulls
        nothing from the source, which is left as the bare loop would
        leave it."""
        if self._closed:
            raise MXNetError("DeviceQueueIter: iterator is closed")
        self._shutdown()
        self.data_iter.reset()
        self._current_batch = None

    def close(self):
        """Stop the worker, drop queued device batches and the staging
        buffers, close the source iterator if it supports close() (unless
        built with ``close_source=False``). Idempotent."""
        if self._closed:
            return
        self._closed = True
        self._shutdown()
        self._q = queue.Queue()  # drop device-batch references
        self._current_batch = None
        if self._close_source:
            inner_close = getattr(self.data_iter, "close", None)
            if callable(inner_close):
                inner_close()

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
