"""Schedule autotuner for the Pallas kernels (ISSUE 10, ROADMAP item 1).

Reference counterpart: the reference framework leaned on cuDNN's
autotuner (``MXNET_CUDNN_AUTOTUNE_DEFAULT``) and marked the concern
"subsumed" by XLA in config.py — but the *Pallas* kernels sit below
XLA's autotuning: their row-tile / channel-block / batch-fold and
flash-attention block sizes were hand-picked constants. TVM
(arXiv:1802.04799) showed schedule search over exactly this tile/block
knob space beats hand schedules, and Relay (arXiv:1810.00952) that the
payoff compounds when tuned schedules are consulted at bind time rather
than baked into call sites. This package is that loop for the Pallas
tier:

- :mod:`.table` — the on-disk schedule table: versioned JSON records
  keyed by ``(kernel, shape, dtype, backend)``, atomic writes
  (``checkpoint.atomic_write_bytes``), a process-local memo so the
  hot-path :func:`schedule_for` lookup is a dict hit, and loud-but-
  non-fatal handling of corrupt/stale tables (a broken table must
  never crash a training job — it logs, falls back to the hand
  defaults, and is rewritten by the next tune).
- :mod:`.harness` — the loop-amortized single-jitted-``lax.scan``
  timing harness (the PR 1 measurement half, shared with
  tools/bench_kernel.py).
- :mod:`.search` — candidate generation over the existing knob space,
  pre-timing pruning (illegal tiles and, where the shape can meet it,
  sub-``MXU_WORK_FLOOR`` candidates — ``mxu_plan`` is the legality/
  work oracle), round-robin candidate timing, and table commits.

Kernel entry points (``fused_block`` fwd/wgrad/dgrad,
``flash_attention``) consult :func:`schedule_for` at trace time with
the current hand defaults as fallback, so an empty table is
bit-identical to the pre-autotuner behavior. ``tools/tune_kernels.py``
runs the sweep offline; ``profiler.tuning_stats`` counts table
hits/misses/fallbacks and records each kernel's chosen schedule.

ISSUE 15 grows the loop with a *learned* half:

- :mod:`.model` — a pure-numpy learned cost model (ridge on log
  plan-summary features) trained on the table's banked timings,
  cross-validated per (kernel, backend), abstaining (exhaustive
  fallback) when under-trained or below the rank-correlation floor.
- ranked sweeps — :func:`sweep_fused`/:func:`sweep_flash` time only
  the model's top-``MXNET_TUNE_TOPK`` candidates (hand default always
  included) and refit the model from every commit.
- :mod:`.background` — :class:`BackgroundTuner`: long training jobs
  tune the shapes they actually traced in bounded slots at drain
  boundaries (armed by ``MXNET_TUNE_BACKGROUND=1``).
"""
from .table import (ScheduleTable, TABLE_VERSION, clear_misses,
                    default_table_path, get_table, make_key,
                    recorded_misses, schedule_for)
from .table import reset as _reset_table
from .search import (FLASH_BLOCKS, FUSED_KINDS, SWEEPABLE_KERNELS,
                     flash_candidates, fused_candidates, sweep_flash,
                     sweep_for_key, sweep_fused)
from .model import (CostModel, CostModelError, MODEL_VERSION,
                    default_model_path, features_from_plan,
                    fit_cost_model, get_model, plan_for)
from .model import reset as _reset_model
from .background import BackgroundTuner


def reset():
    """Drop the process-global table, miss registry, and cost model —
    tests, and long-lived processes that want to pick up externally
    updated files."""
    _reset_table()
    _reset_model()



def rule_kernels():
    """{IR rule name: kernel names it lands on} from the pass
    framework's rule registry (ISSUE 13): a fusion rule *names* the
    Pallas kernel family its rewrite consults, and the autotuner folds
    those names into its sweep set automatically — new fusions become
    searchable schedule-table keys with zero edits here."""
    from ..ir.rules import registered_kernels

    return registered_kernels()


def sweepable_kernels():
    """Kernel names the offline sweep covers by default: the built-in
    families plus every kernel a registered IR rule names (unknown
    rule-named kernels are surfaced by tools/tune_kernels.py as
    unsweepable rather than silently dropped)."""
    names = list(SWEEPABLE_KERNELS)
    for kernels in rule_kernels().values():
        for k in kernels:
            if k not in names:
                names.append(k)
    return tuple(names)


__all__ = [
    "ScheduleTable", "TABLE_VERSION", "default_table_path", "get_table",
    "make_key", "reset", "schedule_for", "recorded_misses", "clear_misses",
    "FLASH_BLOCKS", "FUSED_KINDS", "SWEEPABLE_KERNELS", "flash_candidates",
    "fused_candidates", "rule_kernels", "sweepable_kernels",
    "sweep_flash", "sweep_fused", "sweep_for_key",
    "BackgroundTuner", "CostModel", "CostModelError", "MODEL_VERSION",
    "default_model_path", "features_from_plan", "fit_cost_model",
    "get_model", "plan_for",
]
