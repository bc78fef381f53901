"""Schedule autotuner (ISSUE 10): table, consult wiring, search.

Contracts, all CPU-checkable in interpret mode:

1. **Bit-exactness** — a searched schedule changes only the grid
   tiling, never the math: conv_fwd output is bf16 bit-identical
   across schedules at the CPU bench shapes (the tiling partitions the
   output; each element's contraction runs whole), wgrad/dgrad and the
   f32 stats match to accumulation-order tolerance, and flash
   attention matches across block sizes.
2. **Consult wiring** — kernel entry points pick searched schedules up
   from the on-disk table at trace time (hits/misses/fallbacks counted
   in ``profiler.tuning_stats``); an empty table or ``MXNET_TPU_TUNE=0``
   is bit-identical to the hand defaults; an illegal stored schedule
   falls back loudly instead of crashing.
3. **Corruption** — a truncated/garbage/version-mismatched table file
   logs, behaves as empty, and is rewritten by the next tune. Never a
   crash.
4. **Search mechanics** — illegal candidates (tile > dim, non-dividing
   blocks) are pruned before timing (asserted via the trajectory),
   sub-floor candidates are pruned at the bench shapes where the floor
   is reachable, a bounded sweep commits a winner, and a second sweep
   of the same key is a pure cache hit with zero candidate timings.
5. **CI smoke** — ``tools/tune_kernels.py`` end-to-end (search → table
   commit → cache-hit reload) with a 2-candidate budget at the reduced
   CPU shape; the full-space sweep is ``slow``-tiered.
"""
import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import config, profiler, tune
from mxnet_tpu.base import MXNetError
from mxnet_tpu.kernels import fused_block as fb
import mxnet_tpu.kernels.flash_attention

# the kernels package re-exports the flash_attention FUNCTION under the
# module's name — reach the module itself for monkeypatching
fa = sys.modules["mxnet_tpu.kernels.flash_attention"]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the reduced CPU bench shapes (tools/bench_kernel.py harness-validation
# defaults) — the acceptance criterion's parity shapes
N, HW, CI, CO = 2, 8, 32, 32
CONV_SHAPE = (N, HW, HW, CI, CO, 3, 1)

SWEEP_KW = dict(budget=3, repeats=3, target_sec=0.03, min_iters=5)


@pytest.fixture
def table_path(tmp_path, monkeypatch):
    p = tmp_path / "schedule_table.json"
    monkeypatch.setenv("MXNET_TPU_TUNE_TABLE", str(p))
    monkeypatch.delenv("MXNET_TPU_TUNE", raising=False)
    tune.reset()
    profiler.tuning_reset()
    yield p
    tune.reset()
    profiler.tuning_reset()


def _conv_args(k=3, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (N, HW, HW, CI), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[1], (k, k, CI, CO), jnp.float32).astype(dtype)
    scale = jax.random.uniform(ks[2], (CI,), jnp.float32, 0.5, 1.5)
    bias = jax.random.normal(ks[3], (CI,), jnp.float32) * 0.1
    return x, w, scale, bias


def _qkv(b=2, h=2, s=64, d=16):
    rng = np.random.RandomState(0)
    return tuple(jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
                 for _ in range(3))


def _f32(a):
    return np.asarray(a, np.float32)


# ---------------------------------------------------------------------------
# table
# ---------------------------------------------------------------------------
def test_table_roundtrip_memo_and_reload(table_path):
    t = tune.get_table()
    sched = {"row_tile": 4, "chan_block": 16, "batch_fold": 2}
    t.record("fused_fwd", CONV_SHAPE, "bfloat16", "cpu",
             {"schedule": sched, "ms_per_iter": 0.1})
    assert t.lookup("fused_fwd", CONV_SHAPE, "bfloat16", "cpu") == sched
    # backend / dtype make distinct keys
    assert t.lookup("fused_fwd", CONV_SHAPE, "bfloat16", "tpu") is None
    assert t.lookup("fused_fwd", CONV_SHAPE, "float32", "cpu") is None
    # fresh process-equivalent: a new table object re-reads the file
    tune.reset()
    assert tune.get_table().lookup("fused_fwd", CONV_SHAPE, "bfloat16",
                                   "cpu") == sched
    stats = profiler.tuning_stats()
    assert stats["hits"] == 2 and stats["misses"] == 2
    key = tune.make_key("fused_fwd", CONV_SHAPE, "bfloat16", "cpu")
    assert stats["kernels"][key]["source"] == "table"


def test_concurrent_tables_merge_commits(table_path):
    # two tuner processes sharing one file: a commit re-reads the disk
    # merge base, so a stale process snapshot cannot clobber the other
    # process's winner
    a = tune.ScheduleTable(str(table_path))
    b = tune.ScheduleTable(str(table_path))
    assert b.lookup("fused_fwd", CONV_SHAPE, "bfloat16", "cpu",
                    record_stats=False) is None  # b loads (empty)
    a.record("fused_fwd", CONV_SHAPE, "bfloat16", "cpu",
             {"schedule": {"row_tile": 4}, "ms_per_iter": 0.1})
    b.record("fused_wgrad", CONV_SHAPE, "bfloat16", "cpu",
             {"schedule": {"row_tile": 2}, "ms_per_iter": 0.2})
    fresh = tune.ScheduleTable(str(table_path))
    assert len(fresh) == 2


def test_table_rejects_malformed_record(table_path):
    t = tune.get_table()
    for bad in ({}, {"schedule": {}}, {"schedule": {"nope": 3}},
                {"schedule": {"row_tile": 0}},
                {"schedule": {"row_tile": "4"}}):
        with pytest.raises(ValueError):
            t.record("fused_fwd", CONV_SHAPE, "bfloat16", "cpu", bad)


@pytest.mark.slow   # PR 21: tier-1 wall (tests/README.md)
def test_empty_table_and_knob_off_are_bit_identical(table_path, monkeypatch):
    x, w, scale, bias = _conv_args()
    y_empty, st_empty = fb.conv_fwd(x, w, stride=1,
                                    prologue=(scale, bias, True),
                                    emit_stats=True)
    monkeypatch.setenv("MXNET_TPU_TUNE", "0")
    y_off, st_off = fb.conv_fwd(x, w, stride=1,
                                prologue=(scale, bias, True),
                                emit_stats=True)
    assert np.array_equal(_f32(y_empty), _f32(y_off))
    assert np.array_equal(_f32(st_empty), _f32(st_off))


# ---------------------------------------------------------------------------
# bit-exactness across schedules (acceptance)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sched", [
    {"row_tile": 2, "chan_block": 16, "batch_fold": 1},
    {"row_tile": 4, "chan_block": 32, "batch_fold": 2},
    {"row_tile": 8, "chan_block": 16, "batch_fold": 2},
])
def test_conv_fwd_schedule_parity_bit_exact(sched):
    x, w, scale, bias = _conv_args()
    y0, st0 = fb.conv_fwd(x, w, stride=1, prologue=(scale, bias, True),
                          emit_stats=True)
    y1, st1 = fb.conv_fwd(x, w, stride=1, prologue=(scale, bias, True),
                          emit_stats=True, schedule=sched)
    # tiling partitions the output; each element's contraction runs
    # whole inside one MXU call — bf16 bit-identical
    assert np.array_equal(_f32(y0), _f32(y1))
    # f32 stats accumulate across grid steps in schedule-dependent
    # order — tolerance, not bit equality
    np.testing.assert_allclose(_f32(st0), _f32(st1), rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("sched", [
    {"row_tile": 2, "chan_block": 16, "batch_fold": 2},
    {"row_tile": 4, "chan_block": 32, "batch_fold": 1},
])
@pytest.mark.slow   # PR 21: tier-1 wall (tests/README.md)
def test_conv_grad_schedule_parity(sched):
    x, w, scale, bias = _conv_args()
    g = jax.random.normal(jax.random.PRNGKey(7), (N, HW, HW, CO),
                          jnp.float32).astype(jnp.bfloat16)
    dw0 = fb.conv_wgrad(x, g, (3, 3, CI, CO), stride=1,
                        x_prologue=(scale, bias, True))
    dw1 = fb.conv_wgrad(x, g, (3, 3, CI, CO), stride=1,
                        x_prologue=(scale, bias, True), schedule=sched)
    np.testing.assert_allclose(_f32(dw0), _f32(dw1), rtol=1e-4, atol=1e-2)
    dx0, _ = fb.conv_dgrad(g, w, (N, HW, HW, CI), stride=1)
    dx1, _ = fb.conv_dgrad(g, w, (N, HW, HW, CI), stride=1, schedule=sched)
    np.testing.assert_allclose(_f32(dx0), _f32(dx1), rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("bq,bk", [(32, 32), (16, 64), (64, 16)])
def test_flash_schedule_parity(bq, bk):
    q, k, v = _qkv()
    ref = fa.flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    out = fa.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    np.testing.assert_allclose(_f32(out), _f32(ref), rtol=2e-5, atol=2e-5)
    gref = jax.grad(lambda a: fa.flash_attention(
        a, k, v, causal=True, block_q=128, block_k=128).sum())(q)
    gout = jax.grad(lambda a: fa.flash_attention(
        a, k, v, causal=True, block_q=bq, block_k=bk).sum())(q)
    np.testing.assert_allclose(_f32(gout), _f32(gref), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# trace-time consult wiring
# ---------------------------------------------------------------------------
def test_conv_consults_table_at_trace_time(table_path, monkeypatch):
    sched = {"row_tile": 2, "chan_block": 16, "batch_fold": 1}
    tune.get_table().record("fused_fwd", CONV_SHAPE, "bfloat16",
                            jax.default_backend(),
                            {"schedule": sched, "ms_per_iter": 0.1})
    seen = []
    real_plan = fb._plan_conv

    def spy(*args, **kwargs):
        seen.append(args)
        return real_plan(*args, **kwargs)

    monkeypatch.setattr(fb, "_plan_conv", spy)
    x, w, scale, bias = _conv_args()
    y, _ = fb.conv_fwd(x, w, stride=1, prologue=(scale, bias, True),
                       emit_stats=True)
    # args: (n, ho, wo, ci, co, k, stride, row_tile, chan_block,
    # batch_fold) — the searched knobs must have reached the plan
    assert seen and seen[0][7:] == (2, 16, 1)
    stats = profiler.tuning_stats()
    assert stats["hits"] >= 1
    y_def, _ = fb.conv_fwd(x, w, stride=1, prologue=(scale, bias, True),
                           emit_stats=True, schedule={})
    assert np.array_equal(_f32(y), _f32(y_def))


def test_conv_falls_back_on_illegal_table_entry(table_path):
    # chan_block 7 does not divide co=32: a hand-edited/corrupt entry
    # must fall back to defaults (counted), never crash the job
    tune.get_table().record("fused_fwd", CONV_SHAPE, "bfloat16",
                            jax.default_backend(),
                            {"schedule": {"chan_block": 7},
                             "ms_per_iter": 0.1})
    x, w, scale, bias = _conv_args()
    y, _ = fb.conv_fwd(x, w, stride=1, prologue=(scale, bias, True),
                       emit_stats=True)
    y_def, _ = fb.conv_fwd(x, w, stride=1, prologue=(scale, bias, True),
                           emit_stats=True, schedule={})
    assert np.array_equal(_f32(y), _f32(y_def))
    assert profiler.tuning_stats()["fallbacks"] >= 1


def test_explicit_row_tile_override_skips_table(table_path, monkeypatch):
    tune.get_table().record("fused_fwd", CONV_SHAPE, "bfloat16",
                            jax.default_backend(),
                            {"schedule": {"row_tile": 2}, "ms_per_iter": 1})
    x, w, scale, bias = _conv_args()
    fb.conv_fwd(x, w, stride=1, prologue=(scale, bias, True),
                emit_stats=True, row_tile=4)
    stats = profiler.tuning_stats()
    assert stats.get("hits", 0) == 0  # bench sweeps must pin schedules
    # the env knob is a manual override too: it beats the table (README)
    monkeypatch.setenv("MXNET_TPU_FUSED_ROW_TILE", "4")
    fb.conv_fwd(x, w, stride=1, prologue=(scale, bias, True),
                emit_stats=True)
    assert profiler.tuning_stats().get("hits", 0) == 0


def test_fallback_overwrites_kernels_stat(table_path):
    # a rejected table schedule must not be reported as the chosen one
    tune.get_table().record("fused_fwd", CONV_SHAPE, "bfloat16",
                            jax.default_backend(),
                            {"schedule": {"chan_block": 7},
                             "ms_per_iter": 0.1})
    x, w, scale, bias = _conv_args()
    fb.conv_fwd(x, w, stride=1, prologue=(scale, bias, True))
    key = tune.make_key("fused_fwd", CONV_SHAPE, "bfloat16",
                        jax.default_backend())
    stats = profiler.tuning_stats()
    assert stats["kernels"][key]["source"] == "fallback_illegal"
    assert stats["kernels"][key]["schedule"] is None


def test_flash_consults_table(table_path, monkeypatch):
    q, k, v = _qkv()
    key_shape = (2, 2, 64, 64, 16, 1)
    tune.get_table().record("flash_attention", key_shape, "float32",
                            jax.default_backend(),
                            {"schedule": {"block_q": 32, "block_k": 32},
                             "ms_per_iter": 0.1})
    requested = []
    real_eff = fa.effective_blocks

    def spy(bq, bk, sq, sk):
        requested.append((bq, bk))
        return real_eff(bq, bk, sq, sk)

    monkeypatch.setattr(fa, "effective_blocks", spy)
    out = fa.flash_attention(q, k, v, causal=True)
    assert requested[0] == (32, 32)
    assert profiler.tuning_stats()["hits"] >= 1
    ref = fa.flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    np.testing.assert_allclose(_f32(out), _f32(ref), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# hardened row-tile knob (satellite)
# ---------------------------------------------------------------------------
def test_row_tile_env_knob_strict_and_cached(monkeypatch):
    monkeypatch.setattr(fb, "ROW_TILE", None)
    monkeypatch.setattr(fb, "_ROW_TILE_ENV_CACHE", None)
    monkeypatch.setenv("MXNET_TPU_FUSED_ROW_TILE", "8")
    assert fb._row_tile_default() == 8
    # cache keyed by the raw string: a changed env value still lands
    monkeypatch.setenv("MXNET_TPU_FUSED_ROW_TILE", "4")
    assert fb._row_tile_default() == 4
    for bad in ("banana", "-3", "0", "1.5"):
        monkeypatch.setenv("MXNET_TPU_FUSED_ROW_TILE", bad)
        with pytest.raises(MXNetError, match="MXNET_TPU_FUSED_ROW_TILE"):
            fb._row_tile_default()
    # set_row_tile wins over the env knob
    monkeypatch.setenv("MXNET_TPU_FUSED_ROW_TILE", "8")
    monkeypatch.setattr(fb, "ROW_TILE", 2)
    assert fb._row_tile_default() == 2
    monkeypatch.delenv("MXNET_TPU_FUSED_ROW_TILE")
    monkeypatch.setattr(fb, "ROW_TILE", None)
    assert fb._row_tile_default() == 16


def test_tune_knobs_registered():
    for name in ("MXNET_TPU_TUNE", "MXNET_TPU_TUNE_TABLE"):
        assert name in config.KNOBS, name
        assert config.KNOBS[name][1] == "honored", name


# ---------------------------------------------------------------------------
# corruption (satellite): log + fall back + rewritten by the next tune
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("payload", [
    b"{\"version\": 1, \"entr",                        # truncated
    b"\x00\x01garbage not json",                        # garbage
    b"{\"version\": 999, \"entries\": {}}",            # version mismatch
    b"{\"version\": 1, \"entries\": {\"k\": {\"schedule\": "
    b"{\"row_tile\": \"x\"}}}}",                       # malformed record
    b"[1, 2, 3]",                                       # wrong top level
])
@pytest.mark.slow   # PR 21: tier-1 wall (tests/README.md)
def test_corrupt_table_falls_back_and_is_rewritten(table_path, payload,
                                                   caplog):
    table_path.write_bytes(payload)
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.tune"):
        assert tune.schedule_for("fused_fwd", CONV_SHAPE, "bfloat16",
                                 backend="cpu") is None
    assert any("schedule table" in r.message for r in caplog.records)
    # a training job on top of the corrupt table just runs defaults
    x, w, scale, bias = _conv_args()
    fb.conv_fwd(x, w, stride=1, prologue=(scale, bias, True))
    # ... and the next tune rewrites the file whole
    rep = tune.sweep_fused("fused_fwd", (N, HW, HW, CI), (3, 3, CI, CO),
                           stride=1, **SWEEP_KW)
    assert not rep["cache_hit"]
    data = json.loads(table_path.read_text())
    assert data["version"] == tune.TABLE_VERSION
    assert len(data["entries"]) == 1


# ---------------------------------------------------------------------------
# search mechanics
# ---------------------------------------------------------------------------
@pytest.mark.slow   # PR 21: tier-1 wall (tests/README.md)
def test_sweep_commits_prunes_then_cache_hits(table_path):
    rep = tune.sweep_fused("fused_fwd", (N, HW, HW, CI), (3, 3, CI, CO),
                           stride=1, **SWEEP_KW)
    assert not rep["cache_hit"]
    statuses = [e["status"] for e in rep["trajectory"]]
    # illegal candidates (row_tile 16/32 > 8 rows, chan_block 64..256 >
    # co=32, batch folds > n=2) are pruned BEFORE timing, with reasons
    pruned = [e for e in rep["trajectory"]
              if e["status"] == "pruned_illegal"]
    assert pruned and all(e["reason"] for e in pruned)
    assert any("row_tile" in e["reason"] for e in pruned)
    assert any("chan_block" in e["reason"] for e in pruned)
    assert statuses.count("default") == 1
    assert rep["n_timed"] <= SWEEP_KW["budget"]
    assert all("ms_per_iter" in e for e in rep["trajectory"]
               if e["status"] in ("default", "timed"))
    # winner is consultable and keeps the kernel bit-identical
    win = tune.schedule_for("fused_fwd", CONV_SHAPE, "bfloat16")
    assert win == rep["winner"]["schedule"]
    x, w, scale, bias = _conv_args()
    y, _ = fb.conv_fwd(x, w, stride=1, prologue=(scale, bias, True))
    y_def, _ = fb.conv_fwd(x, w, stride=1, prologue=(scale, bias, True),
                           schedule={})
    assert np.array_equal(_f32(y), _f32(y_def))
    # second sweep of the same key: pure cache hit, zero timings
    profiler.tuning_reset()
    rep2 = tune.sweep_fused("fused_fwd", (N, HW, HW, CI), (3, 3, CI, CO),
                            stride=1, **SWEEP_KW)
    assert rep2["cache_hit"] and rep2["n_timed"] == 0
    assert profiler.tuning_stats()["hits"] >= 1


def test_sweep_flash_commits_and_cache_hits(table_path):
    rep = tune.sweep_flash(2, 2, 64, 64, 16, causal=False, **SWEEP_KW)
    assert not rep["cache_hit"] and rep["n_timed"] >= 2
    assert any(e["status"] == "pruned_illegal" for e in rep["trajectory"])
    rep2 = tune.sweep_flash(2, 2, 64, 64, 16, causal=False, **SWEEP_KW)
    assert rep2["cache_hit"] and rep2["n_timed"] == 0


def test_floor_pruning_at_bench_shapes():
    # the TPU bench shape (batch 64, hw 14, 256ch) CAN meet the 256^3
    # floor, so legal-but-sub-floor candidates are pruned; classification
    # only — nothing timed
    entries = tune.fused_candidates("fused_fwd", (64, 14, 14, 256),
                                    (3, 3, 256, 256), 1)
    floor_pruned = [e for e in entries if e["status"] == "pruned_floor"]
    survivors = [e for e in entries if e["status"] == "candidate"]
    assert floor_pruned and survivors
    assert all(e["work"] < fb.MXU_WORK_FLOOR for e in floor_pruned)
    assert all(e["work"] >= fb.MXU_WORK_FLOOR for e in survivors)
    # at the tiny CPU shape the floor is unreachable — nothing pruned
    # on work, or the smoke would have an empty search space
    tiny = tune.fused_candidates("fused_fwd", (N, HW, HW, CI),
                                 (3, 3, CI, CO), 1)
    assert not any(e["status"] == "pruned_floor" for e in tiny)
    assert any(e["status"] == "candidate" for e in tiny)


def test_flash_candidates_dedup_and_clamp():
    entries = tune.flash_candidates(64, 64)
    # 128/256 clamp to 64 at seq 64: illegal (they duplicate another
    # candidate's program)
    assert any(e["status"] == "pruned_illegal"
               and "clamp" in e["reason"] for e in entries)
    legal = [tuple(sorted(e["schedule"].items()))
             for e in entries if e["status"] in ("default", "candidate")]
    assert len(legal) == len(set(legal))


def test_tuning_stats_ride_dump_profile(tmp_path, monkeypatch):
    profiler.tuning_reset()
    profiler.tuning_record(hits=2, fallbacks=1, kernel="k1",
                           schedule={"row_tile": 4}, source="table")
    out = tmp_path / "profile.json"
    monkeypatch.setitem(profiler._STATE, "filename", str(out))
    profiler.dump_profile()
    payload = json.loads(out.read_text())
    assert payload["tuningStats"]["hits"] == 2
    assert payload["tuningStats"]["fallbacks"] == 1
    assert payload["tuningStats"]["kernels"]["k1"]["source"] == "table"
    profiler.tuning_reset()
    assert profiler.tuning_stats() == {}


# ---------------------------------------------------------------------------
# CI smoke (satellite): tools/tune_kernels.py end-to-end
# ---------------------------------------------------------------------------
def _run_tuner(table, extra=()):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "tune_kernels.py"),
         "--cpu", "--budget", "2", "--repeats", "3",
         "--kernels", "fused_fwd,flash_attention",
         "--table", table] + list(extra),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow   # PR 21: tier-1 wall (tests/README.md)
def test_tune_kernels_cli_end_to_end(tmp_path):
    table = str(tmp_path / "table.json")
    rep = _run_tuner(table)
    # fused_fwd + flash at the bench shape + flash at the ISSUE 12
    # decode shape (seq_q=1 — part of the default sweep so decode
    # blocks are tunable)
    assert len(rep["tune"]) == 3
    decode_keys = [k for k, r in rep["tune"].items()
                   if r["kernel"] == "flash_attention"
                   and r["shape"][2] == 1]
    assert len(decode_keys) == 1
    dec = rep["tune"][decode_keys[0]]
    assert dec["shape"][5] == 0  # causal=0: decode masks by length
    # block_q clamps to 1 at seq_q=1 (the effective_blocks fix); the
    # search space is the block_k axis
    assert dec["winner"]["schedule"]["block_q"] == 1
    assert any(e["status"] in ("timed", "skipped_budget", "candidate")
               and e["schedule"]["block_q"] == 1
               and e["schedule"]["block_k"]
               != dec["winner"]["default_schedule"]["block_k"]
               for e in dec["trajectory"])
    for r in rep["tune"].values():
        assert not r["cache_hit"]
        assert any(e["status"] == "pruned_illegal" for e in r["trajectory"])
        assert r["winner"]["schedule"]
        assert r["winner"]["default_ms_per_iter"] > 0
    # search -> table commit -> cache-hit reload -> kernel consult,
    # across processes: the second run times NOTHING
    rep2 = _run_tuner(table)
    assert all(r["cache_hit"] and r["n_timed"] == 0
               for r in rep2["tune"].values())
    assert rep2["tuning_stats"]["hits"] >= 2


@pytest.mark.slow
def test_tune_kernels_full_sweep(tmp_path):
    """Full kernel set at default budget — the offline tuning workflow
    as a user runs it (slow tier; the default tier covers the bounded
    smoke above)."""
    table = str(tmp_path / "table.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "tune_kernels.py"),
         "--cpu", "--table", table],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout.strip().splitlines()[-1])
    # 3 fused kinds + flash at the bench shape + flash decode shape
    assert len(rep["tune"]) == 5
    assert all(not r["cache_hit"] for r in rep["tune"].values())
