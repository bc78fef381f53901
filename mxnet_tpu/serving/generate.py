"""Paged KV-cache machinery for generative serving (ISSUE 12).

The workload nncase targets (PAPERS.md, arXiv:2512.21571) —
autoregressive LLM decoding — differs structurally from the one-shot
forwards the serving tier batched so far: every request carries
device-resident state (its KV cache), sequence lengths vary wildly, and
requests finish at different decode steps. Two pieces live here; the
continuous-batching decode loop (:class:`~.broker.GenerateServer`) owns
them from ``serving/broker.py``:

- :class:`PagePool` — an exact-accounting fixed-size-block allocator
  for KV-cache memory (vLLM's PagedAttention idea): a finished
  request's pages are recycled the moment it completes instead of
  pinning ``max_seq_len`` per batch slot. Exhaustion raises the typed
  :class:`PagePoolExhausted` — backpressure, never an OOM or a silent
  stall — and the accounting is asserted leak-free in tests.
- :class:`GenerativePredictor` — one transformer bound for incremental
  decode: a ladder of prefill programs (prompt padded to page-aligned
  power-of-two buckets, the PR 6 ladder idea) that fill per-layer K/V
  pages, plus ONE decode program (``slots`` queries, 1 token each)
  that writes each token's K/V row into the pool in place and attends
  the pages named by each slot's block table where they lie (on a TPU
  the ``kernels/paged_decode.py`` kernel; see ``models/transformer.py``).
  The program chooses the next token itself (:func:`_decode_program`) and
  can take it from the step before on the device, so the broker keeps one
  step in flight (``decode_ahead`` / ``read_step``).
  The big cache buffer is donated to every call on accelerators (the
  PR 6 donation rule: skipped on CPU where it only warns), so the
  decode program holds one pool; compiled
  programs share the serving tier's :class:`ExecutableCache`, and the
  decode attention's ``block_k`` is consulted from the PR 10 schedule
  table at trace time (``tools/tune_kernels.py`` sweeps the
  decode shape).

Page 0 of the cache is the scratch page: never handed out, it absorbs
writes from inactive slots and padded prompt tails so the compiled
programs stay shape-static without ever corrupting live pages.
"""
from __future__ import annotations

import threading

import numpy as np

from .. import config
from ..base import MXNetError
from .predictor import ExecutableCache, ServingError


class GenerateError(ServingError):
    """Generative-serving failure (bad knob, bad request, dead loop)."""


class PagePoolExhausted(GenerateError):
    """The KV page pool has no free page for this allocation. Typed
    backpressure: at admission the request simply waits in the queue
    for completions to recycle pages; a request that could NEVER fit
    (or a mid-decode growth the pool cannot serve) fails fast with
    this error instead of stalling silently or OOMing the device."""


def _env_positive_int(name):
    if config.get(name) is None:
        raise GenerateError("unknown knob %s" % name)
    try:
        return config.get_positive_int(name)
    except MXNetError as e:
        raise GenerateError(str(e))


def _env_nonneg_int(name):
    try:
        return config.get_nonneg_int(name)
    except MXNetError as e:
        raise GenerateError(str(e))


def _env_strict_bool(name):
    try:
        return config.get_strict_bool(name)
    except MXNetError as e:
        raise GenerateError(str(e))


class PagePool:
    """Fixed-size-block allocator with exact accounting and per-page
    refcounts (ISSUE 16: copy-on-write prefix sharing).

    Page ids run 1..num_pages (0 is the cache's scratch page). ``alloc``
    raises :class:`PagePoolExhausted` when the request cannot be
    satisfied — it never partially allocates — and hands each page out
    at refcount 1. ``ref`` takes an extra reference on a live page (a
    second request sharing a cached prefix page, or the prefix index
    pinning one); ``unref`` drops one reference and only returns the
    page to the free list when the count reaches zero. ``free`` is the
    historical alias for ``unref``. Both reject double-drops and
    foreign ids loudly: a page leak (or double recycle) silently
    corrupts another request's KV state, so the accounting must be
    exact by construction — after every holder drops its reference,
    ``in_use == 0`` and ``allocs == frees`` (pages handed out == pages
    returned), asserted by the torture test."""

    def __init__(self, num_pages):
        num_pages = int(num_pages)
        if num_pages < 1:
            raise GenerateError("PagePool: need >= 1 page, got %d"
                                % num_pages)
        self.num_pages = num_pages
        self._free = list(range(num_pages, 0, -1))  # pop() hands out 1 first
        self._refcount = {}                         # page id -> live refs
        self._lock = threading.Lock()
        self.high_water = 0
        self.allocs = 0
        self.frees = 0
        self.refs = 0              # extra references taken (sharing events)
        self.ref_high_water = 0    # max refcount any single page reached

    def alloc(self, n):
        """n pages as a list of ids, or PagePoolExhausted (all-or-nothing).
        Each page comes out at refcount 1, owned by the caller."""
        n = int(n)
        if n < 0:
            raise GenerateError("PagePool.alloc: n must be >= 0, got %d" % n)
        with self._lock:
            if n > len(self._free):
                raise PagePoolExhausted(
                    "page pool exhausted: need %d page(s), %d free of %d "
                    "(MXNET_GENERATE_POOL_BYTES)"
                    % (n, len(self._free), self.num_pages))
            pages = [self._free.pop() for _ in range(n)]
            for p in pages:
                self._refcount[p] = 1
            self.allocs += n
            if len(self._refcount) > self.high_water:
                self.high_water = len(self._refcount)
            if n and self.ref_high_water < 1:
                self.ref_high_water = 1
            return pages

    def ref(self, pages):
        """Take one extra reference on each (live) page — sharing, not
        allocation: no free page is consumed. Foreign ids raise."""
        with self._lock:
            for p in pages:
                if p not in self._refcount:
                    raise GenerateError(
                        "PagePool.ref: page %r is not allocated "
                        "(cannot share a free or foreign page)" % (p,))
            for p in pages:
                rc = self._refcount[p] + 1
                self._refcount[p] = rc
                self.refs += 1
                if rc > self.ref_high_water:
                    self.ref_high_water = rc

    def unref(self, pages):
        """Drop one reference per page; a page whose count reaches zero
        returns to the free list. Double-drops and foreign ids raise."""
        with self._lock:
            for p in pages:
                if p not in self._refcount:
                    raise GenerateError(
                        "PagePool.free: page %r is not allocated "
                        "(double free or foreign id)" % (p,))
            for p in pages:
                rc = self._refcount[p] - 1
                if rc:
                    self._refcount[p] = rc
                else:
                    del self._refcount[p]
                    self._free.append(p)
                    self.frees += 1

    def free(self, pages):
        """Alias of :meth:`unref` (the pre-sharing name every holder —
        broker slot vacate, tests — already uses)."""
        self.unref(pages)

    def refcount(self, page):
        """Current reference count of ``page`` (0 when free)."""
        with self._lock:
            return self._refcount.get(page, 0)

    @property
    def in_use(self):
        with self._lock:
            return len(self._refcount)

    @property
    def free_pages(self):
        with self._lock:
            return len(self._free)

    def stats(self):
        with self._lock:
            shared = sum(1 for rc in self._refcount.values() if rc > 1)
            return {"num_pages": self.num_pages,
                    "in_use": len(self._refcount),
                    "free": len(self._free),
                    "high_water": self.high_water,
                    "allocs": self.allocs, "frees": self.frees,
                    "refs": self.refs, "shared": shared,
                    "ref_high_water": self.ref_high_water}


class _PrefixNode:
    __slots__ = ("page", "children", "last_used")

    def __init__(self, page, clock):
        self.page = page
        self.children = {}
        self.last_used = clock


class PrefixIndex:
    """Radix-tree index over full KV pages keyed by token-id page runs
    (ISSUE 16 prefix sharing).

    Each node maps one ``page_size``-token run to the pool page holding
    that run's K/V; a path from the root spells out a prompt prefix in
    whole pages. The index itself holds ONE pool reference per indexed
    page (taken at :meth:`insert`, dropped at eviction), so an indexed
    page stays alive after the request that prefilled it finishes —
    that reference is what turns a private page into a shareable one.

    - :meth:`match` walks the longest indexed prefix of a prompt,
      capped at ``(prompt_len - 1) // page_size`` pages so the tail
      prefill always has >= 1 token — the structural form of the
      copy-on-write rule: a partial (or final) page is always
      re-prefilled privately, never shared, hence shared pages are
      never written. Matched pages are ref'd on the caller's behalf
      (the caller unrefs them exactly once, same as its private pages).
    - :meth:`insert` indexes a just-prefilled prompt's full pages,
      taking an extra reference on each newly indexed page; runs
      already indexed are only LRU-touched (the request keeps its
      private duplicate — dedup happens for FUTURE requests via match).
    - :meth:`evict_lru` drops the least-recently-matched leaf —
      called under pool pressure so sharing never causes a
      :class:`PagePoolExhausted` a no-sharing run would avoid, and to
      keep the index under ``max_pages`` when one is set.
    """

    def __init__(self, page_size, max_pages=0):
        self.page_size = int(page_size)
        if self.page_size < 1:
            raise GenerateError("PrefixIndex: page_size must be >= 1, "
                                "got %d" % self.page_size)
        self.max_pages = int(max_pages or 0)
        self._root = {}
        self._clock = 0
        self._pages = 0
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.insertions = 0
        self.evictions = 0

    def _runs(self, tokens, n):
        ps = self.page_size
        return [tuple(int(t) for t in tokens[i * ps:(i + 1) * ps])
                for i in range(n)]

    def match(self, tokens, pool):
        """Longest indexed full-page prefix of ``tokens`` — at most
        ``(len(tokens) - 1) // page_size`` pages (see class docstring).
        Returns the page-id list with one reference per page taken on
        ``pool`` for the caller; the whole path is LRU-touched."""
        limit = max(0, (len(tokens) - 1) // self.page_size)
        pages = []
        with self._lock:
            self._clock += 1
            node_map, touched = self._root, []
            for run in self._runs(tokens, limit):
                node = node_map.get(run)
                if node is None:
                    break
                touched.append(node)
                pages.append(node.page)
                node_map = node.children
            for node in touched:
                node.last_used = self._clock
            if pages:
                pool.ref(pages)
                self.hits += 1
            else:
                self.misses += 1
        return pages

    def insert(self, tokens, pages, pool):
        """Index the full pages of a just-prefilled prompt: run i →
        ``pages[i]``. Only runs fully covered by the prompt are indexed
        (``len(tokens) // page_size`` of them — a final page that the
        decode loop will keep writing is still mutable and stays
        private). Newly indexed pages cost one extra pool reference;
        existing runs keep their already-indexed page. Returns the
        number of pages newly indexed."""
        n = min(len(tokens) // self.page_size, len(pages))
        added = 0
        with self._lock:
            self._clock += 1
            node_map = self._root
            for i, run in enumerate(self._runs(tokens, n)):
                node = node_map.get(run)
                if node is None:
                    pool.ref([pages[i]])
                    node = _PrefixNode(pages[i], self._clock)
                    node_map[run] = node
                    self._pages += 1
                    self.insertions += 1
                    added += 1
                else:
                    node.last_used = self._clock
                node_map = node.children
        if self.max_pages:
            while self.pages > self.max_pages:
                if not self.evict_lru(pool):
                    break
        return added

    def evict_lru(self, pool):
        """Drop the least-recently-matched LEAF node (leaves first so a
        prefix chain stays contiguous) and release the index's
        reference on its page — the page only becomes free once no
        live request shares it. Returns True when a node was evicted,
        False on an empty index."""
        with self._lock:
            victim = None          # (last_used, parent_map, run, node)
            stack = [(self._root, run, node)
                     for run, node in self._root.items()]
            while stack:
                parent, run, node = stack.pop()
                if node.children:
                    stack.extend((node.children, r, ch)
                                 for r, ch in node.children.items())
                elif victim is None or node.last_used < victim[0]:
                    victim = (node.last_used, parent, run, node)
            if victim is None:
                return False
            _, parent, run, node = victim
            del parent[run]
            self._pages -= 1
            self.evictions += 1
            page = node.page
        pool.unref([page])
        return True

    def clear(self, pool):
        """Evict everything (release every index reference)."""
        while self.evict_lru(pool):
            pass

    @property
    def pages(self):
        with self._lock:
            return self._pages

    def stats(self):
        with self._lock:
            return {"pages": self._pages, "hits": self.hits,
                    "misses": self.misses, "insertions": self.insertions,
                    "evictions": self.evictions,
                    "max_pages": self.max_pages}


def _model_module(config_):
    """The module that offers a configuration's cache and programs:
    the one its ``module`` attribute names, else the transformer.  A model
    module offers ``init_kv_cache`` (any pytree of page pools under one
    block table), ``kv_page_bytes``, ``make_prefill_fn`` and
    ``make_decode_fn``; optionally ``make_extend_fn`` (prefix tails,
    speculation), ``_decode_block_k``, ``compute_dtype_leaves(config)``
    (the leaves its programs read only in the compute dtype) and
    ``served_leaves(config)`` (leaves to bind transposed, under another
    name: name -> (name bound, axes)); and
    ``decode_counters(config)``, the names of what its decode program
    counts on the device and returns beside the logits (none for the
    transformer)."""
    name = getattr(config_, "module", None)
    if name is None:
        from ..models import transformer

        return transformer
    import importlib

    return importlib.import_module(name)


def _decode_program(model, config_, slots, max_pages_per_slot, page_size,
                    block_k, mesh=None):
    """The decode step every model module is served through: the module's own
    ``make_decode_fn``, with the next token chosen on the device.

    fn(params, cache, last (slots + n,) int32, host_ids (slots,) int32,
    from_host (slots,) bool, positions, block_tables, active) →
    (cache', (logits (slots, V), chosen (slots + n,) int32)), n the length
    of the module's ``decode_counters``.  A slot's token is ``host_ids`` where
    ``from_host`` (its prefill chose it, on the host) and the id the step
    before chose, ``last[:slots]``, otherwise: a step can be dispatched
    before the host has read the one before.  ``chosen`` holds
    ``argmax(logits, -1)``, the first of equal maxima as ``np.argmax``
    takes it, and behind it the module's counters: one small read a step.
    The function is named ``decode``, so a trace shows ``jit_decode``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    step = model.make_decode_fn(config_, slots, max_pages_per_slot, page_size,
                                block_k=block_k, mesh=mesh)
    counted = bool(model.decode_counters(config_))

    def decode(params, cache, last, host_ids, from_host, positions,
               block_tables, active):
        tokens = jnp.where(from_host, host_ids, last[:slots])
        cache, out = step(params, cache, tokens, positions, block_tables,
                          active)
        logits, counts = out if counted else (out, None)
        chosen = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if counted:
            chosen = jnp.concatenate([chosen, counts.astype(jnp.int32)])
        if mesh is not None:
            # fed back as the next step's ``last``: keep it placed as the
            # first one is, so the step stays one program
            chosen = jax.lax.with_sharding_constraint(
                chosen, NamedSharding(mesh, P()))
        return cache, (logits, chosen)

    return decode


class GenerativePredictor:
    """One model bound for prefill + single-token decode.

    Parameters
    ----------
    config_ : models.transformer.TransformerConfig, or any configuration
        whose ``module`` attribute names its model module
        (:func:`_model_module`, e.g. ``models.mla_moe.LatentMoEConfig``)
        The model architecture (``dtype`` is the cache/compute dtype).
    params : dict
        ``init_params``-layout arrays (numpy or jax); frozen onto the
        device once.  A ``jax.Array`` is bound as it is given, in its
        own dtype and without a copy through the host (bfloat16 weights
        stay bfloat16); anything else is copied to the device.  The
        leaves the model module names in ``compute_dtype_leaves(config)``
        (the transformer's four stacked matrices), which its programs
        read only through a cast to ``config.dtype``, are cast to it on
        the device as they are bound, one leaf at a time, where they are
        wider than it: the programs then read the same values without
        casting every stacked matrix on every run, and no wider copy is
        kept beside them (nothing here trains them).  Every other leaf
        keeps its dtype.  The leaves the module names in
        ``served_leaves(config)`` are bound transposed, under the name it
        gives them: a copy made on the device in the place of the leaf.
        :meth:`pool_stats` reports what the bind held (``param_bytes``),
        cast (``params_cast``) and transposed (``params_transposed``).
    slots : int, optional
        Batch-slot count of the decode program
        (``MXNET_GENERATE_SLOTS``).
    page_size : int, optional
        Tokens per KV page (``MXNET_GENERATE_PAGE_SIZE``).
    pool_bytes : int, optional
        KV page-pool budget in bytes (``MXNET_GENERATE_POOL_BYTES``);
        0/None auto-sizes to ``slots * max_pages_per_slot`` pages —
        every slot can hold a full-context request, so decode-time
        exhaustion is impossible and paging only buys recycling speed.
        A smaller explicit budget oversubscribes: admission
        backpressures on :class:`PagePoolExhausted`.
    max_ctx : int, optional
        Per-slot context bound (prompt + generated), default
        ``config.max_len``; rounded down to a whole page count.
    block_k : int, optional
        Decode attention's key columns per online-softmax turn (whole
        pages in the TPU kernel); default consults the schedule
        table at :func:`models.transformer.decode_schedule_shape`.
    cache : ExecutableCache, optional
        Shared compiled-program LRU (the serving tier's); private
        unbounded cache by default.
    mesh : jax.sharding.Mesh, optional
        Bind the model SHARDED across a replica group (ISSUE 20):
        weights placed per ``models.transformer.param_specs`` (megatron
        column/row over the mesh's ``mp``/``tp`` axis) and the paged KV
        cache sharded over its head-major lane axis (``kv_cache_spec``)
        so every chip holds 1/mp of every page. Mutually exclusive with
        ``device``; the programs are GSPMD-partitioned automatically,
        their Pallas kernels (prefill's flash, decode's paged attention)
        under ``shard_map`` over the heads each chip holds.
    """

    def __init__(self, config_, params, *, slots=None, page_size=None,
                 pool_bytes=None, max_ctx=None, block_k=None, device=None,
                 cache=None, model_name=None, mesh=None):
        import jax
        import jax.numpy as jnp

        tfm = self._model = _model_module(config_)
        self.config = config_
        self.slots = _env_positive_int("MXNET_GENERATE_SLOTS") \
            if slots is None else int(slots)
        if self.slots < 1:
            raise GenerateError("GenerativePredictor: slots must be >= 1, "
                                "got %d" % self.slots)
        self.page_size = _env_positive_int("MXNET_GENERATE_PAGE_SIZE") \
            if page_size is None else int(page_size)
        if self.page_size < 1:
            raise GenerateError("GenerativePredictor: page_size must be "
                                ">= 1, got %d" % self.page_size)
        ctx_bound = config_.max_len if max_ctx is None \
            else min(int(max_ctx), config_.max_len)
        self.max_pages_per_slot = ctx_bound // self.page_size
        if self.max_pages_per_slot < 1:
            raise GenerateError(
                "GenerativePredictor: page_size %d exceeds the context "
                "bound %d" % (self.page_size, ctx_bound))
        self.max_ctx = self.max_pages_per_slot * self.page_size

        c = config_
        cdt = jnp.dtype(c.dtype)
        self.page_bytes = int(tfm.kv_page_bytes(c, self.page_size))
        if pool_bytes is None:
            pool_bytes = _env_nonneg_int("MXNET_GENERATE_POOL_BYTES")
        pool_bytes = int(pool_bytes or 0)
        if pool_bytes > 0:
            num_pages = pool_bytes // self.page_bytes
            if num_pages < self.max_pages_per_slot:
                raise GenerateError(
                    "MXNET_GENERATE_POOL_BYTES=%d holds %d page(s) of %d "
                    "bytes — smaller than one full-context request "
                    "(%d pages); raise the budget or shrink max_ctx/"
                    "page_size" % (pool_bytes, num_pages, self.page_bytes,
                                   self.max_pages_per_slot))
        else:
            num_pages = self.slots * self.max_pages_per_slot
        self.pool = PagePool(num_pages)

        if device is not None and hasattr(device, "jax_device"):
            device = device.jax_device()
        if mesh is not None and device is not None:
            raise GenerateError(
                "GenerativePredictor: pass mesh= OR device=, not both "
                "(a sharded bind owns the whole group's placement)")
        self._device = device
        self._mesh = mesh
        self._group_size = int(mesh.devices.size) if mesh is not None else 1
        if device is not None:
            platform = device.platform
        elif mesh is not None:
            platform = mesh.devices.flat[0].platform
        else:
            platform = jax.default_backend()
        self._donate = platform != "cpu"
        self._exec_cache = cache if cache is not None \
            else ExecutableCache(None)
        self._cache_key = model_name if model_name is not None \
            else "gen-%d" % id(self)
        self._dtype_name = str(cdt)

        narrow = getattr(tfm, "compute_dtype_leaves", lambda _c: ())(c)
        served = getattr(tfm, "served_leaves", lambda _c: {})(c)
        cast, transposed = [], []

        def bind(k, a):
            # on the device, so a float32 leaf and its cast (or its
            # transpose) are held together for one leaf at a time
            if k in narrow and a.dtype.itemsize > cdt.itemsize:
                cast.append(k)
                a = a.astype(cdt)
            if k in served:
                k, axes = served[k]
                transposed.append(k)
                a = jnp.transpose(a, axes)
            return k, a

        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            pspecs = tfm.param_specs(c, mesh)

            def put(a, spec=None):
                return jax.device_put(
                    jnp.asarray(np.asarray(a)),
                    NamedSharding(mesh, spec if spec is not None else P()))

            self._params = dict(bind(k, put(v, pspecs.get(k)))
                                for k, v in params.items())
            self._kv = put(tfm.init_kv_cache(c, num_pages, self.page_size),
                           tfm.kv_cache_spec(mesh))
        else:
            def put(a):
                if not isinstance(a, jax.Array):
                    a = jnp.asarray(np.asarray(a))
                return jax.device_put(a, device) if device is not None else a

            self._params = dict(bind(k, put(v)) for k, v in params.items())
            self._kv = jax.tree.map(
                put, tfm.init_kv_cache(c, num_pages, self.page_size))
        self._bind_stats = {
            "param_bytes": sum(int(a.nbytes) for a in self._params.values()),
            "params_cast": len(cast), "params_transposed": len(transposed)}
        if block_k is None:
            pick = getattr(tfm, "_decode_block_k", None)
            block_k = pick(c, self.slots, self.max_ctx) if pick else 0
        self.block_k = int(block_k)
        # what the decode program counts on the device, read with the ids
        # it chose: the names, and the last step ``decode`` read
        self._counter_names = tuple(tfm.decode_counters(c))
        self.step_counters = {}
        # the newest decode step's ``chosen`` (ids, then counters), on the
        # device: the next step takes its tokens from it.  Placed as the
        # program's outputs are, so feeding one back is the same call
        last = jnp.zeros((self.slots + len(self._counter_names),), jnp.int32)
        placed = [a for a in jax.tree.leaves((self._params, self._kv))
                  if a.committed]
        if placed:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            last = jax.device_put(
                last, NamedSharding(mesh, P()) if mesh is not None
                else placed[0].sharding)
        self._last = last

        # prefill bucket ladder: page-aligned powers of two up to the
        # context bound (the PR 6 ladder idea at page granularity)
        buckets, b = [], self.page_size
        while b < self.max_ctx:
            buckets.append(b)
            b *= 2
        buckets.append(self.max_ctx)
        self.prefill_buckets = tuple(buckets)
        self._lock = threading.Lock()

    # -- compiled programs ---------------------------------------------------
    def _jit(self, fn):
        import jax

        return jax.jit(fn, donate_argnums=(1,) if self._donate else ())

    def _config_fingerprint(self):
        """Everything a compiled program's closure bakes in besides the
        bucket/slot tag: the model architecture, the page geometry and
        the mesh a sharded bind's prefill shard_maps over.
        Part of every cache key so two predictors sharing one
        ExecutableCache under the same model name can never reuse each
        other's programs."""
        import dataclasses

        mesh = None if self._mesh is None else (
            tuple(self._mesh.shape.items()),
            tuple(d.id for d in self._mesh.devices.flat))
        return (tuple(sorted(dataclasses.asdict(self.config).items())),
                self.page_size, self.max_pages_per_slot, self.block_k, mesh)

    def _prefill_exec(self, bucket):
        tfm = self._model
        key = (self._cache_key, ("prefill", bucket),
               self._config_fingerprint(), self._dtype_name)
        return self._exec_cache.get_or_build(
            key, lambda: self._jit(tfm.make_prefill_fn(
                self.config, self.page_size, mesh=self._mesh)))

    def _decode_exec(self):
        tfm = self._model
        key = (self._cache_key, ("decode", self.slots),
               self._config_fingerprint(), self._dtype_name)
        return self._exec_cache.get_or_build(
            key, lambda: self._jit(_decode_program(
                tfm, self.config, self.slots, self.max_pages_per_slot,
                self.page_size, self.block_k, self._mesh)))

    def _extend_exec(self, batch, steps):
        tfm = self._model
        if not hasattr(tfm, "make_extend_fn"):
            raise GenerateError(
                "%s offers no extend program (prefix tails and speculation "
                "need one)" % tfm.__name__)
        key = (self._cache_key, ("extend", batch, steps),
               self._config_fingerprint(), self._dtype_name)
        return self._exec_cache.get_or_build(
            key, lambda: self._jit(tfm.make_extend_fn(
                self.config, batch, steps, self.max_pages_per_slot,
                self.page_size, block_k=self.block_k)))

    # -- request surface -----------------------------------------------------
    def pages_needed(self, prompt_len):
        return -(-int(prompt_len) // self.page_size)

    def pick_bucket(self, prompt_len):
        for b in self.prefill_buckets:
            if b >= prompt_len:
                return b
        raise GenerateError(
            "prompt of %d tokens exceeds the per-slot context bound %d"
            % (prompt_len, self.max_ctx))

    def prefill(self, tokens, pages):
        """Run one prompt (1-D int array) through the prefill program,
        scattering K/V into ``pages`` (ids from :attr:`pool`); returns
        the last position's logits as numpy (V,)."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        length = int(tokens.shape[0])
        if length < 1:
            raise GenerateError("prefill: empty prompt")
        if self.pages_needed(length) != len(pages):
            raise GenerateError(
                "prefill: %d-token prompt needs %d page(s), got %d"
                % (length, self.pages_needed(length), len(pages)))
        bucket = self.pick_bucket(length)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :length] = tokens
        page_arr = np.zeros((bucket // self.page_size,), np.int32)
        page_arr[:len(pages)] = pages   # tail pages hit scratch (0)
        fn = self._prefill_exec(bucket)
        with self._lock:
            self._kv, logits = fn(self._params, self._kv, padded,
                                  np.int32(length), page_arr)
        return np.asarray(logits)

    def decode(self, tokens, positions, block_tables, active):
        """One decode step over all ``slots``; returns numpy logits
        (slots, V). ``tokens[b]`` is written at ``positions[b]`` into
        the page its slot's ``block_tables`` row names; inactive slots
        write to scratch and return zero logits."""
        logits, chosen = self._decode(
            tokens, np.ones((self.slots,), bool), positions, block_tables,
            active)
        self.step_counters = self.read_step(chosen)[1]
        return np.asarray(logits)

    def decode_ahead(self, host_ids, from_host, positions, block_tables,
                     active):
        """Dispatch one decode step and read nothing: a slot takes
        ``host_ids[b]`` where ``from_host[b]`` and the id the step before
        chose for it otherwise, which the host need not have read yet.
        Returns what :meth:`read_step` reads, on the device."""
        return self._decode(host_ids, from_host, positions, block_tables,
                            active)[1]

    def read_step(self, chosen):
        """Wait for a dispatched step: the ids it chose, numpy (slots,)
        int32, and the module's counters of that step by name."""
        chosen = np.asarray(chosen)
        return chosen[:self.slots], dict(zip(
            self._counter_names, chosen[self.slots:].tolist()))

    def _decode(self, host_ids, from_host, positions, block_tables, active):
        # the arguments are copied: the device may read them after this
        # returns, and the broker's tables change under a step in flight
        fn = self._decode_exec()
        with self._lock:
            self._kv, (logits, chosen) = fn(
                self._params, self._kv, self._last,
                np.array(host_ids, np.int32),
                np.array(from_host, bool),
                np.array(positions, np.int32),
                np.array(block_tables, np.int32),
                np.array(active, bool))
            self._last = chosen
        return logits, chosen

    def extend(self, tokens, positions, block_tables, valid):
        """Multi-token append (ISSUE 16): run ``tokens`` (S, T) at
        ``positions`` (S, T) against each slot's cached pages in one
        compiled call; returns numpy logits (S, T, V). Invalid entries
        write to scratch and return zero logits. Serves both the
        shared-prefix tail prefill (S = 1, T = a prefill bucket) and
        the speculative verify step (S = slots, T = k + 1)."""
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim != 2:
            raise GenerateError("extend: tokens must be (batch, steps), "
                                "got shape %r" % (tokens.shape,))
        S, T = tokens.shape
        fn = self._extend_exec(S, T)
        with self._lock:
            self._kv, logits = fn(
                self._params, self._kv, tokens,
                np.asarray(positions, np.int32),
                np.asarray(block_tables, np.int32),
                np.asarray(valid, bool))
        return np.asarray(logits)

    def extend_tail(self, tokens, start_pos, pages):
        """Prefill the uncovered TAIL of a prefix-matched prompt:
        ``tokens`` (the tail, 1-D) start at absolute position
        ``start_pos`` and attend the full block table ``pages``
        (shared prefix pages + the request's private tail pages).
        Tail length is padded up the same prefill bucket ladder.
        Returns the last tail position's logits as numpy (V,) — the
        request's first generated token, same contract as
        :meth:`prefill`. Every tail position lies at or past
        ``start_pos`` >= the shared region, so shared pages are never
        written (copy-on-write by construction)."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = int(tokens.shape[0])
        if n < 1:
            raise GenerateError("extend_tail: empty tail")
        if start_pos % self.page_size != 0:
            raise GenerateError(
                "extend_tail: start_pos %d is not page-aligned (the "
                "shared prefix covers whole pages)" % start_pos)
        if start_pos + n > self.max_ctx:
            raise GenerateError(
                "extend_tail: tail of %d token(s) at position %d exceeds "
                "the per-slot context bound %d" % (n, start_pos,
                                                  self.max_ctx))
        bucket = self.pick_bucket(n)
        tok = np.zeros((1, bucket), np.int32)
        tok[0, :n] = tokens
        pos = np.arange(start_pos, start_pos + bucket,
                        dtype=np.int32)[None, :]
        valid = np.zeros((1, bucket), bool)
        valid[0, :n] = True
        bt = np.zeros((1, self.max_pages_per_slot), np.int32)
        bt[0, :len(pages)] = pages
        logits = self.extend(tok, pos, bt, valid)
        return logits[0, n - 1]

    def pool_stats(self):
        """The page pool's counters, and what the bind holds:
        ``param_bytes`` (every bound leaf, one copy of each),
        ``params_cast`` (the leaves cast to the compute dtype) and
        ``params_transposed`` (the leaves bound transposed,
        ``served_leaves``)."""
        return dict(self.pool.stats(), **self._bind_stats)

    def sharded_stats(self):
        """Measured per-chip bytes of the sharded bind (ISSUE 20):
        params and the paged KV cache, counting only shards resident on
        the first mesh device — the KV pages split over heads, so each
        chip holds ~1/mp of every page. Records into the profiler's
        ``mpStats`` gauge group. Raises on a single-device bind."""
        if self._mesh is None:
            raise GenerateError(
                "sharded_stats: predictor was not bound on a mesh "
                "(pass mesh= to the constructor)")
        dev0 = self._mesh.devices.flat[0]

        def chip_bytes(arr):
            return sum(int(s.data.nbytes) for s in arr.addressable_shards
                       if s.device == dev0)

        # read the cache under the lock: a concurrent prefill/decode
        # donates self._kv, and a donated array's shards are gone
        with self._lock:
            kv_chip = chip_bytes(self._kv)
            kv_total = int(self._kv.nbytes)
        param_chip = sum(chip_bytes(v) for v in self._params.values())
        mp = int(dict(self._mesh.shape).get(
            "mp", dict(self._mesh.shape).get("tp", 1)))
        from .. import profiler

        profiler.mp_record(group_size=self._group_size, mp_size=mp,
                           param_bytes_per_chip=param_chip,
                           live_bytes_per_chip=param_chip + kv_chip)
        return {"group_size": self._group_size, "mp_size": mp,
                "param_bytes_per_chip": param_chip,
                "kv_bytes_per_chip": kv_chip,
                "kv_bytes_total": kv_total}
