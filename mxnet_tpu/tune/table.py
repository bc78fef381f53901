"""On-disk schedule table: searched Pallas schedules keyed by
``(kernel, shape, dtype, backend)``.

Design constraints (ISSUE 10):

- **Hot path is a dict hit.** Kernel entry points call
  :func:`schedule_for` at trace time; after the first lookup of a key
  the answer (including the negative answer) sits in a process-local
  memo, so re-traces cost one dict ``get``.
- **Versioned, atomic, corruption-proof.** The table is one JSON file
  (``{"version": 1, "entries": {key: record}}``) written through
  ``checkpoint.atomic_write_bytes`` (tmp + fsync + rename — a crash
  mid-commit leaves the old table). A truncated/garbage/version-
  mismatched file logs a warning, behaves as empty (hand defaults),
  and is fully rewritten by the next tune commit — it must never
  crash a training job.
- **Backend-keyed.** A schedule searched on the CPU interpreter says
  nothing about the MXU; ``backend`` (``jax.default_backend()``) is
  part of the key so CPU smoke tables can never leak into TPU runs.

Location: ``MXNET_TPU_TUNE_TABLE`` when set, else
``~/.cache/mxnet_tpu/schedule_table.json``. ``MXNET_TPU_TUNE=0``
disables the trace-time consult entirely (hand defaults, zero reads).
"""
from __future__ import annotations

import json
import logging
import os
import threading

from .. import config

log = logging.getLogger("mxnet_tpu.tune")

TABLE_VERSION = 1

# schedule knobs a record may carry, per kernel family; anything else
# in a loaded schedule is rejected (the entry falls back to defaults)
_KNOWN_KNOBS = frozenset(
    ("row_tile", "chan_block", "batch_fold", "block_q", "block_k"))


def default_table_path():
    override = config.get("MXNET_TPU_TUNE_TABLE")
    if override:
        return override
    return os.path.join(os.path.expanduser("~"), ".cache", "mxnet_tpu",
                        "schedule_table.json")


def make_key(kernel, shape, dtype, backend):
    """The table/report key: ``kernel|d0xd1x...|dtype|backend``."""
    dims = "x".join(str(int(d)) for d in shape)
    return "%s|%s|%s|%s" % (kernel, dims, dtype, backend)


# ---------------------------------------------------------------------------
# miss registry (ISSUE 15): every trace-time consult that found no
# table entry records WHAT was missing — (kernel, shape, dtype,
# backend), enough to reconstruct a sweep — so the background tuner
# can time ranked candidates for exactly the shapes the job traced.
# Process-local, bounded, cleared when a commit satisfies the key.
# ---------------------------------------------------------------------------
_MISS_LOCK = threading.Lock()
_MISSES = {}          # key -> {key, kernel, shape, dtype, backend, count}
_MISS_CAP = 512


def _record_miss(key, kernel, shape, dtype, backend):
    with _MISS_LOCK:
        m = _MISSES.get(key)
        if m is not None:
            m["count"] += 1
            return
        if len(_MISSES) >= _MISS_CAP:
            return
        _MISSES[key] = {"key": key, "kernel": str(kernel),
                        "shape": tuple(int(d) for d in shape),
                        "dtype": str(dtype), "backend": str(backend),
                        "count": 1}


def recorded_misses():
    """Snapshot of the schedule-table misses this process recorded via
    trace-time consults (``schedule_for``), insertion-ordered — the
    background tuner's work queue."""
    with _MISS_LOCK:
        return [dict(m) for m in _MISSES.values()]


def clear_miss(key):
    with _MISS_LOCK:
        _MISSES.pop(key, None)


def clear_misses():
    with _MISS_LOCK:
        _MISSES.clear()


def _valid_schedule(schedule):
    if not isinstance(schedule, dict) or not schedule:
        return False
    for k, v in schedule.items():
        if k not in _KNOWN_KNOBS:
            return False
        if not isinstance(v, int) or isinstance(v, bool) or v < 1:
            return False
    return True


class ScheduleTable:
    """One JSON schedule table + its process-local memo."""

    def __init__(self, path=None):
        self.path = path or default_table_path()
        self._lock = threading.Lock()
        self._memo = {}        # key -> schedule dict | None (negative)
        self._entries = None   # key -> full record; None until loaded
        self.load_error = None

    # -- load / persist ----------------------------------------------------
    def _load_locked(self):
        if self._entries is not None:
            return
        self._entries = {}
        try:
            with open(self.path, "r", encoding="utf-8") as f:
                raw = f.read()
        except FileNotFoundError:
            return
        except OSError as e:
            self.load_error = "unreadable: %s" % e
            log.warning("schedule table %s unreadable (%s); using default "
                        "schedules", self.path, e)
            return
        try:
            data = json.loads(raw)
            if not isinstance(data, dict):
                raise ValueError("top level is %s, not an object"
                                 % type(data).__name__)
            version = data.get("version")
            if version != TABLE_VERSION:
                raise ValueError("version %r != %d" % (version,
                                                       TABLE_VERSION))
            entries = data["entries"]
            if not isinstance(entries, dict):
                raise ValueError("entries is %s, not an object"
                                 % type(entries).__name__)
            loaded = {}
            for key, rec in entries.items():
                if not (isinstance(rec, dict)
                        and _valid_schedule(rec.get("schedule"))):
                    raise ValueError("malformed record for key %r" % key)
                loaded[key] = rec
        except (ValueError, KeyError, TypeError) as e:
            # corrupt/stale table: behave as empty — the kernels fall
            # back to their hand defaults and the next tune commit
            # rewrites the whole file
            self.load_error = str(e)
            log.warning(
                "schedule table %s is corrupt or from another version "
                "(%s); falling back to default schedules — the next "
                "tools/tune_kernels.py run rewrites it", self.path, e)
            return
        self._entries = loaded

    def _persist_locked(self):
        payload = {"version": TABLE_VERSION, "entries": self._entries}
        data = json.dumps(payload, indent=1, sort_keys=True).encode("utf-8")
        d = os.path.dirname(os.path.abspath(self.path))
        if d:
            os.makedirs(d, exist_ok=True)
        from ..checkpoint import atomic_write_bytes

        atomic_write_bytes(self.path, data)
        self.load_error = None

    # -- API ---------------------------------------------------------------
    def lookup(self, kernel, shape, dtype, backend, record_stats=True):
        """Schedule dict for the key, or None. Counts a table hit or
        miss in ``profiler.tuning_stats`` (``record_stats=False`` for
        introspection that must not skew the counters)."""
        key = make_key(kernel, shape, dtype, backend)
        if key in self._memo:
            sched = self._memo[key]
        else:
            with self._lock:
                self._load_locked()
                rec = self._entries.get(key)
                sched = dict(rec["schedule"]) if rec else None
                self._memo[key] = sched
        if record_stats:
            from .. import profiler

            if sched is not None:
                profiler.tuning_record(hits=1, kernel=key,
                                       schedule=dict(sched), source="table")
            else:
                profiler.tuning_record(misses=1)
                _record_miss(key, kernel, shape, dtype, backend)
        return dict(sched) if sched else None

    def reload(self):
        """Drop the in-memory entries AND the consult memo so the next
        read re-reads the table file — how a long-lived process picks
        up another job's commits (the background tuner calls this once
        per drain slot, so its tuned-elsewhere check and the trace-time
        consults both see cross-process winners; without it ``lookup``
        would serve the memoized miss forever)."""
        with self._lock:
            self._entries = None
            self.load_error = None
            self._memo = {}

    def entry(self, kernel, shape, dtype, backend):
        """The full stored record (schedule + timings), or None."""
        with self._lock:
            self._load_locked()
            rec = self._entries.get(make_key(kernel, shape, dtype, backend))
            return dict(rec) if rec else None

    def entries(self):
        """Snapshot of every stored record keyed by table key — the
        cost model's training-row source (ISSUE 15)."""
        with self._lock:
            self._load_locked()
            return {k: dict(v) for k, v in self._entries.items()}

    def record(self, kernel, shape, dtype, backend, record):
        """Commit one winner record (atomic whole-file rewrite).

        The merge base is re-read from disk at commit time, so two
        tuner processes sharing one table file (a manual sweep next to
        bench.py's tune variant) don't clobber each other's winners
        with stale process-lifetime snapshots; the remaining race is
        two commits in the same instant, which a tuning tool can live
        with. Banked ``timings`` rows merge against the re-read base
        the same way (fresh measurement of a schedule wins): a
        topk-bounded ranked sweep or background slot GROWS the cost
        model's training set, never shrinks another sweep's bank
        (ISSUE 15)."""
        if not _valid_schedule(record.get("schedule")):
            raise ValueError("record.schedule must be a non-empty dict of "
                             "known integer knobs >= 1, got %r"
                             % (record.get("schedule"),))
        key = make_key(kernel, shape, dtype, backend)
        with self._lock:
            self._entries = None
            self.load_error = None
            self._load_locked()
            prev = self._entries.get(key)
            if prev and prev.get("timings"):
                if record.get("timings"):
                    # loading validates only the top-level schedule, so
                    # a hand-edited/foreign-build banked row can be
                    # anything — skip what the merge key cannot digest
                    # (corrupt-data-behaves-as-absent, like the model's
                    # _record_rows), never break every future commit
                    # for the key
                    merged = {}
                    for t in list(prev["timings"]) + list(record["timings"]):
                        try:
                            merged[frozenset(t["schedule"].items())] = t
                        except (AttributeError, KeyError, TypeError):
                            continue
                    record = dict(record, timings=list(merged.values()))
                else:
                    # a winner-only commit (PR 10-era caller, the
                    # --compare recommit) must never destroy the bank
                    record = dict(record, timings=prev["timings"])
            self._entries[key] = dict(record, kernel=kernel,
                                      shape=[int(d) for d in shape],
                                      dtype=str(dtype), backend=backend)
            self._persist_locked()
            self._memo[key] = dict(record["schedule"])
        clear_miss(key)   # a commit satisfies the recorded miss
        return key

    def __len__(self):
        with self._lock:
            self._load_locked()
            return len(self._entries)


# ---------------------------------------------------------------------------
# process-global table + the trace-time consult API
# ---------------------------------------------------------------------------
_GLOBAL_LOCK = threading.Lock()
_GLOBAL = None  # (path, ScheduleTable)


def get_table(path=None):
    """The process-global table for ``path`` (default: knob-resolved).
    A changed ``MXNET_TPU_TUNE_TABLE`` between calls gets a fresh
    table; the common case is one table for the process lifetime."""
    global _GLOBAL
    resolved = path or default_table_path()
    with _GLOBAL_LOCK:
        if _GLOBAL is None or _GLOBAL[0] != resolved:
            _GLOBAL = (resolved, ScheduleTable(resolved))
        return _GLOBAL[1]


def reset():
    """Drop the process-global table (memo included) and the miss
    registry — tests, and long-lived processes that want to pick up an
    externally updated table file."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        _GLOBAL = None
    clear_misses()


def schedule_for(kernel, shape, dtype, backend=None):
    """The trace-time consult the kernel entry points use.

    Returns the searched schedule dict for
    ``(kernel, shape, dtype, backend)`` or None (caller falls back to
    its hand defaults — an empty table is bit-identical to the
    pre-autotuner behavior). ``MXNET_TPU_TUNE=0`` short-circuits to
    None without touching the table or the counters.
    """
    if not config.get_bool("MXNET_TPU_TUNE", True):
        return None
    if backend is None:
        import jax

        backend = jax.default_backend()
    return get_table().lookup(kernel, tuple(shape), str(dtype), backend)
