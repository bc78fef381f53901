"""Background tuning inside training jobs (ISSUE 15).

The PR 10 sweep is an offline chore; this module makes tuning
something long training jobs do *continuously*: a
:class:`BackgroundTuner`, armed by ``MXNET_TUNE_BACKGROUND=1``, steals
**bounded idle slots at drain boundaries** — the points where the PR 5
dispatch-ahead pipeline has already been drained (epoch end's
``get_params``, checkpoint quiesce) — and times one ranked candidate
set for a shape the job actually traced.

Safety contract (the README "Autotuning" section documents it):

- **Drain-boundary only.** ``Module.fit`` calls :meth:`on_drain` right
  after the epoch-end ``get_params``/``set_params`` pair, i.e. after
  the dispatch-ahead pipeline blocked to empty — never inside the
  steady-state step loop, so pipeline/inflight counters stay flat.
- **Bounded per-slot budget.** One missed key per slot, at most
  ``MXNET_TUNE_BG_BUDGET`` timed programs (hand default included),
  with short calibration targets — a slot costs a bounded sliver of
  an epoch.
- **Zero effect when there is nothing to do.** The work queue is the
  schedule table's miss registry (``table.recorded_misses`` — filled
  by the trace-time ``schedule_for`` consults), so a job whose shapes
  are all tuned, or that never traces a Pallas kernel, pays nothing.
  ``MXNET_TPU_TUNE=0`` disables the consult and therefore the tuner.
- **Never crashes training.** A failed sweep logs, drops the miss,
  and the job continues; commits ride the table's atomic
  merge-base-re-reading path, so two concurrent jobs sharing one
  table file cannot clobber each other's winners.

Winners are committed atomically, so the *next* trace of the same
shape (and any later job) picks them up — tuning becomes a property
of running training, not a separate tool invocation.
"""
from __future__ import annotations

import logging
import os

from .. import config, profiler
from . import search
from .table import clear_miss, get_table, recorded_misses

log = logging.getLogger("mxnet_tpu.tune")


class BackgroundTuner:
    """Steals bounded tuning slots at a training job's drain
    boundaries; see the module docstring for the safety contract."""

    def __init__(self, budget=2, table=None, logger=None, sweep_kw=None):
        from ..context import kernel_platform

        self.budget = int(budget)
        self._table = table if table is not None else get_table()
        self._log = logger or log
        on_tpu = kernel_platform() == "tpu"
        # bounded per-slot timing discipline: short calibration target,
        # few repeats — a slot is a sliver of an epoch, not a bench run
        self._sweep_kw = dict(
            repeats=2,
            target_sec=0.2 if on_tpu else 0.02,
            min_iters=100 if on_tpu else 2)
        if sweep_kw:
            self._sweep_kw.update(sweep_kw)

    @classmethod
    def from_env(cls, logger=None):
        """The arming gate ``Module.fit`` consults: returns a tuner
        when ``MXNET_TUNE_BACKGROUND=1`` (strict bool — malformed
        raises naming the knob), else None. ``MXNET_TPU_TUNE=0`` also
        disarms: with the trace-time consult off no misses are
        recorded, so there is nothing to tune. Only rank 0 of a
        multi-worker job arms: every worker traces the same shapes, so
        N workers sweeping the same miss at the same drain boundary
        would pay N bounded slots for one winner — rank 0 tunes,
        everyone picks the commit up at the next trace."""
        if not config.get_strict_bool("MXNET_TUNE_BACKGROUND"):
            return None
        if not config.get_bool("MXNET_TPU_TUNE", True):
            return None
        rank = (os.environ.get("DMLC_WORKER_ID")
                or os.environ.get("DMLC_RANK") or "0")
        try:
            rank = int(rank)
        except ValueError:
            rank = 0
        import jax

        if rank != 0 or jax.process_index() != 0:
            return None
        return cls(budget=config.get_positive_int("MXNET_TUNE_BG_BUDGET"),
                   logger=logger)

    def pending(self):
        """Misses with a sweep recipe that the table has not satisfied
        yet — what the next slots will tune, oldest trace first.
        Re-reads the table file (one bounded read), so another job's
        commits clear their misses here instead of this process's
        memoized negative serving forever; recipe-less misses are
        dropped (nothing will ever tune them)."""
        from . import model as cost_model_mod

        self._table.reload()   # see another job's commits, not the memo
        # same for the model: an external refit (tune_kernels, another
        # job's ranked sweep) must un-abstain this job's slots
        cost_model_mod.get_model(
            cost_model_mod.model_path_for(self._table)).reload()
        out = []
        for miss in recorded_misses():
            if miss["kernel"] not in search.SWEEPABLE_KERNELS:
                clear_miss(miss["key"])   # no sweep recipe: don't retry
                continue
            if self._table.lookup(miss["kernel"], miss["shape"],
                                  miss["dtype"], miss["backend"],
                                  record_stats=False) is not None:
                clear_miss(miss["key"])   # another job tuned it already
                continue
            out.append(miss)
        return out

    def on_drain(self):
        """One bounded tuning slot: sweep the oldest pending miss
        (ranked when the model is usable — ``MXNET_TUNE_RANKER``
        semantics apply unchanged) and commit the winner atomically.
        Returns the sweep report, or None when nothing was pending.
        Exceptions never propagate — background tuning must not crash
        the training job."""
        for miss in self.pending():
            profiler.tuning_record(bg_slots=1)
            try:
                rep = search.sweep_for_key(
                    miss["kernel"], miss["shape"], miss["dtype"],
                    backend=miss["backend"], table=self._table,
                    budget=self.budget, **self._sweep_kw)
            except Exception as e:   # noqa: BLE001 — never crash training
                clear_miss(miss["key"])
                self._log.warning("background tune of %s failed: %s",
                                  miss["key"], e)
                return None
            clear_miss(miss["key"])
            profiler.tuning_record(bg_commits=1)
            self._log.info(
                "background tune committed %s -> %s (%d timed, %.2fs)",
                miss["key"], rep["winner"]["schedule"], rep["n_timed"],
                rep.get("wall_s") or 0.0)
            return rep
        return None
