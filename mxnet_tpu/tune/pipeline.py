"""Training-pipeline ranking: remat x layout choices priced by the
learned cost model (ISSUE 19 tentpole, third leg).

PR 15's loop ranks *kernel schedules* — tile sizes for one Pallas
call. The same machinery prices *graph-level pipeline* choices: should
this training graph run with selective remat? with the layout pass?
Each candidate pipeline is compiled once and featurized from the
compiler's OWN analyses (``TrainStep.compiled_memory_stats``: peak /
temp bytes from ``memory_analysis()``, FLOPs and bytes-accessed from
``cost_analysis()``) plus the pass gauges (save/recompute site counts,
transposes cancelled), mapped onto the ``plan_summary`` feature keys
so the one :class:`~.model.CostModel` learns both levels.

Discipline is identical to the ranked kernel sweeps:

- **abstain-to-default** — no model, too few banked rows, or a
  validation correlation below the floor means the sweep times every
  candidate (exhaustive) and the trace-time consult
  (:func:`pipeline_for`) returns the hand default; predicted vs
  measured ms ride the sweep report and ``tuningStats``.
- **one table** — winners commit to the shared
  :class:`~.table.ScheduleTable` under the constant kernel name
  ``train_pipeline`` (so the model groups pipeline rows across
  graphs), keyed by a structural graph FINGERPRINT folded into the
  shape dims (node names excluded: two builds of the same
  architecture share an entry). Banked timings embed their feature
  plans, so :meth:`~.model.CostModel.fit_from_table` trains on them
  with zero changes.
- **no miss registry** — a pipeline key miss is a fallback, not
  background-tuner work (``sweep_for_key`` has no recipe for graphs);
  ``pipeline_for`` counts hits/misses/fallbacks itself.

``tools/tune_pipeline.py`` runs the sweep;
``tools/dump_graph.py --train`` shows the per-pass plan a choice lowers
to.
"""
from __future__ import annotations

import hashlib
import time

from .. import config
from ..base import MXNetError
from .table import get_table, make_key

PIPELINE_KERNEL = "train_pipeline"

# schedule codes (table schedules are ints >= 1 by contract)
REMAT_CODES = {"off": 1, "pass": 2, "conv": 3}
LAYOUT_CODES = {"off": 1, "on": 2}
_REMAT_NAMES = {v: k for k, v in REMAT_CODES.items()}
_LAYOUT_NAMES = {v: k for k, v in LAYOUT_CODES.items()}

# the abstain-mode choice: today's TrainStep defaults, bit-identical
# to a job that never heard of pipeline ranking
HAND_DEFAULT = {"remat": "off", "layout": "off"}


def candidate_pipelines():
    """The enumerable pipeline space: remat off|pass|conv x layout
    off|on. Small by design — each candidate costs one XLA
    compilation to featurize."""
    return [{"remat": r, "layout": l}
            for r in ("off", "pass", "conv")
            for l in ("off", "on")]


def schedule_of(choice):
    """Encode a pipeline choice as a table schedule (known int knobs)."""
    try:
        return {"remat": REMAT_CODES[choice["remat"]],
                "layout": LAYOUT_CODES[choice["layout"]]}
    except KeyError as e:
        raise MXNetError("unknown pipeline choice field/value: %s in %r"
                         % (e, choice))


def choice_of(schedule):
    """Decode a table schedule back into a pipeline choice; unknown
    codes raise (a corrupt entry must not silently train differently)."""
    try:
        return {"remat": _REMAT_NAMES[int(schedule["remat"])],
                "layout": _LAYOUT_NAMES[int(schedule["layout"])]}
    except (KeyError, TypeError, ValueError):
        raise MXNetError("not a pipeline schedule: %r" % (schedule,))


def graph_fingerprint(symbol):
    """Structural md5 over the graph: op names, sorted attrs, arity and
    input topology indices — node NAMES excluded, so two builds of the
    same architecture (auto-named differently) share a table entry."""
    h = hashlib.md5()
    nodes = symbol._topo()
    index = {id(n): i for i, n in enumerate(nodes)}
    for n in nodes:
        if n.is_variable():
            h.update(b"var;")
            continue
        h.update(n.op.name.encode())
        for k in sorted(n.attrs):
            h.update(("|%s=%s" % (k, n.attrs[k])).encode())
        for inp, idx in n.inputs:
            h.update(("|%d.%d" % (index[id(inp)], idx)).encode())
        h.update(b";")
    return h.hexdigest()


def pipeline_table_shape(symbol, batch_shape):
    """The table-key shape dims: the fingerprint's leading 32 bits
    folded in as an int dim, then the data batch shape — make_key only
    speaks int dims, and this keeps distinct graphs/batch shapes in
    distinct entries."""
    return (int(graph_fingerprint(symbol)[:8], 16),) + tuple(
        int(d) for d in batch_shape)


def featurize(stats, n_nodes, n_save=0, n_recompute=0,
              transposes_cancelled=0):
    """Map one compiled candidate onto the ``plan_summary`` feature
    keys (the CostModel join contract): m/k/n/work carry the XLA
    analyses, calls/nb/th/bco the graph and pass gauges. All values
    are floored to 1 inside ``features_from_plan``."""
    return {
        "m": int(stats.get("peak_bytes", 0)),
        "k": int(stats.get("bytes_accessed", 0)),
        "n": int(stats.get("flops", 0)),
        "work": int(stats.get("temp_bytes", 0)),
        "calls": int(n_nodes),
        "grid": (1, 1, 1),
        "nb": int(n_save) + 1,
        "th": int(n_recompute) + 1,
        "bco": int(transposes_cancelled) + 1,
    }


def _step_kwargs(choice):
    """TrainStep ctor kwargs realizing a pipeline choice."""
    remat = choice["remat"]
    return {
        "remat": False if remat == "off" else remat,
        "train_passes": ("layout",) if choice["layout"] == "on" else (),
    }


def build_train_step(symbol, optimizer, choice, **kw):
    """A TrainStep realizing ``choice`` over ``symbol`` (sweep helper;
    also how a caller applies :func:`pipeline_for`'s decision)."""
    from ..parallel.spmd import TrainStep

    merged = dict(kw)
    merged.update(_step_kwargs(choice))
    return TrainStep(symbol, optimizer, **merged)


def _compile_candidate(symbol, optimizer, choice, batch, data_shapes,
                       seed, step_kw):
    """Build + compile one candidate; returns (TrainStep, carry, plan)
    where plan is the featurization dict."""
    import jax

    ts = build_train_step(symbol, optimizer, choice, **step_kw)
    params, opt_state, aux = ts.init_params(data_shapes, seed=seed)
    carry = ts.place(params, opt_state, aux)
    stats = ts.compiled_memory_stats(carry, batch, jax.random.PRNGKey(0))
    n_nodes = sum(1 for n in ts.symbol._topo() if not n.is_variable())
    plan = featurize(
        stats, n_nodes,
        n_save=ts._remat_plan.n_save if ts._remat_plan else 0,
        n_recompute=ts._remat_plan.n_recompute if ts._remat_plan else 0)
    return ts, carry, stats, plan


def _time_candidate(ts, carry, batch, steps):
    """Median-free mean ms/step over ``steps`` post-warmup steps (the
    compile already happened in featurization, so step 0 is warm)."""
    import jax

    key = jax.random.PRNGKey(1)
    carry, loss = ts(carry, batch, key)        # warmup / donation settle
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        carry, loss = ts(carry, batch, key)
    jax.block_until_ready(loss)
    return (time.perf_counter() - t0) * 1e3 / max(steps, 1)


def sweep_train_pipelines(symbol, optimizer, batch, *, table=None,
                          backend=None, ranked=None, topk=None, steps=3,
                          seed=0, data_names=("data",), step_kw=None):
    """Compile + featurize every candidate pipeline for ``symbol``,
    rank with the cost model (abstain -> exhaustive), time the
    survivors end-to-end, commit the winner to the schedule table and
    refit the model from the banked rows — the graph-level mirror of
    ``search.sweep_fused``.

    ``batch`` is a dict of host/device arrays covering the data AND
    label names ``TrainStep`` expects; timing runs ``steps`` steps per
    survivor after one warmup. Returns the sweep report (trajectory
    with predicted + measured ms per candidate, ranker mode, winner).
    """
    import numpy as np

    from . import model as cost_model_mod
    from .. import profiler
    from .search import _resolve_ranker

    t_start = time.perf_counter()
    if backend is None:
        import jax

        backend = jax.default_backend()
    table = table if table is not None else get_table()
    ranked, topk = _resolve_ranker(ranked, topk)
    step_kw = dict(step_kw or {})
    step_kw.setdefault("data_names", tuple(data_names))
    data_shapes = {n: tuple(batch[n].shape) for n in step_kw["data_names"]}
    batch_shape = data_shapes[step_kw["data_names"][0]]
    shape = pipeline_table_shape(symbol, batch_shape)
    dtype = str(batch[step_kw["data_names"][0]].dtype)

    entries = []
    for choice in candidate_pipelines():
        status = "default" if choice == HAND_DEFAULT else "candidate"
        entries.append({"choice": dict(choice),
                        "schedule": schedule_of(choice), "status": status})

    # featurize: one compile per candidate (this is the sweep's cost)
    compiled = {}
    for e in entries:
        ts, carry, stats, plan = _compile_candidate(
            symbol, optimizer, e["choice"], batch, data_shapes, seed,
            step_kw)
        compiled[id(e)] = (ts, carry)
        e["plan"] = plan
        e["peak_bytes"] = stats["peak_bytes"]
        e["temp_bytes"] = stats["temp_bytes"]

    # rank (the _apply_ranking discipline, on embedded plans)
    cands = [e for e in entries if e["status"] == "candidate"]
    rank_info = {"mode": "exhaustive", "abstained": False}
    if ranked:
        m = cost_model_mod.get_model(cost_model_mod.model_path_for(table))
        ok, why = m.usable(PIPELINE_KERNEL, backend)
        if not ok:
            profiler.tuning_record(ranker_abstains=1)
            rank_info = {"mode": "exhaustive", "abstained": True,
                         "reason": why}
        else:
            pred = m.predict(PIPELINE_KERNEL, backend,
                             [e["plan"] for e in cands])
            order = np.argsort(pred, kind="mergesort")
            keep = set(int(i) for i in order[:topk])
            skipped = 0
            for i, e in enumerate(cands):
                e["predicted_ms"] = round(float(pred[i]), 6)
                if i not in keep:
                    e["status"] = "skipped_ranked"
                    skipped += 1
            profiler.tuning_record(candidates_ranked=len(cands),
                                   timings_skipped=skipped)
            rank_info = {
                "mode": "ranked", "abstained": False, "topk": topk,
                "n_scored": len(cands), "n_skipped": skipped,
                "group": cost_model_mod.group_key(PIPELINE_KERNEL,
                                                  backend),
                "val_corr": (m.group(PIPELINE_KERNEL, backend)
                             or {}).get("val_corr")}

    # time the default + surviving candidates
    timed = [e for e in entries if e["status"] in ("default", "candidate")]
    for e in timed:
        ts, carry = compiled[id(e)]
        e["ms_per_iter"] = round(_time_candidate(ts, carry, batch, steps),
                                 5)

    default = next(e for e in timed if e["status"] == "default")
    winner = min(timed, key=lambda e: e["ms_per_iter"])
    rec = {
        "schedule": dict(winner["schedule"]),
        "ms_per_iter": winner["ms_per_iter"],
        "default_schedule": dict(default["schedule"]),
        "default_ms_per_iter": default["ms_per_iter"],
        "speedup_vs_default": round(
            default["ms_per_iter"] / winner["ms_per_iter"], 3)
        if winner["ms_per_iter"] else 1.0,
        # banked rows EMBED their plans: plan_for has no recipe for
        # graphs, so the model's _record_rows must never need it here
        "timings": [{"schedule": dict(e["schedule"]),
                     "ms_per_iter": e["ms_per_iter"],
                     "plan": dict(e["plan"])} for e in timed],
    }
    table.record(PIPELINE_KERNEL, shape, dtype, backend, rec)
    key = make_key(PIPELINE_KERNEL, shape, dtype, backend)
    profiler.tuning_record(kernel=key,
                           schedule=dict(winner["schedule"]),
                           source="sweep")
    report = {
        "key": key, "kernel": PIPELINE_KERNEL, "shape": list(shape),
        "dtype": dtype, "backend": backend,
        "fingerprint": graph_fingerprint(symbol),
        "trajectory": [
            {k: v for k, v in e.items() if k != "plan"} for e in entries],
        "n_candidates": len(entries),
        "n_timed": len(timed),
        "n_skipped_ranked": sum(1 for e in entries
                                if e["status"] == "skipped_ranked"),
        "ranker": rank_info,
        "winner": {"choice": dict(winner["choice"]),
                   "schedule": dict(winner["schedule"]),
                   "ms_per_iter": winner["ms_per_iter"],
                   "peak_bytes": winner["peak_bytes"],
                   "speedup_vs_default": rec["speedup_vs_default"]},
    }
    try:
        fit_rep = cost_model_mod.fit_cost_model(table)
        report["model_refit"] = fit_rep["fit"]
    except cost_model_mod.CostModelError as e:
        report["model_refit_error"] = str(e)
    report["wall_s"] = round(time.perf_counter() - t_start, 4)
    return report


def pipeline_for(symbol, batch_shape, dtype="float32", backend=None,
                 table=None):
    """Trace-time consult: the committed pipeline choice for this
    graph fingerprint + batch shape, or the hand default.

    Returns ``(choice, source)`` with source ``"table"`` or
    ``"default"``. Abstain-to-default discipline: tuning disabled, no
    entry, or an undecodable schedule all return :data:`HAND_DEFAULT`
    (today's TrainStep behavior) and count a fallback; never raises on
    a missing entry and never enqueues background-tuner work (there is
    no sweep recipe reconstructable from a table key alone)."""
    from .. import profiler

    if not config.get_bool("MXNET_TPU_TUNE", True):
        return dict(HAND_DEFAULT), "default"
    if backend is None:
        import jax

        backend = jax.default_backend()
    table = table if table is not None else get_table()
    shape = pipeline_table_shape(symbol, batch_shape)
    key = make_key(PIPELINE_KERNEL, shape, str(dtype), backend)
    sched = table.lookup(PIPELINE_KERNEL, shape, str(dtype), backend,
                         record_stats=False)
    if sched is None:
        profiler.tuning_record(misses=1, fallbacks=1)
        return dict(HAND_DEFAULT), "default"
    try:
        choice = choice_of(sched)
    except MXNetError:
        profiler.tuning_record(fallbacks=1)
        return dict(HAND_DEFAULT), "default"
    profiler.tuning_record(hits=1, kernel=key, schedule=dict(sched),
                           source="table")
    return choice, "table"
