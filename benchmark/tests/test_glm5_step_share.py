"""The GLM-5 decode step's counts by hand at a tiny size, the shares a
context made by hand gives, and that every reader this cell adds returns
nothing on a context without its scope or counter (a parent's run)."""
import json
import os

import pytest

from benchmark import harness
from benchmark.readers import glm5_step_share as share

M = dict(vocab=10, d_model=8, n_heads=2, n_layers=3, n_dense_layers=1, d_ff=12,
         d_expert=4, n_experts=16, experts_per_token=2, held_experts=[0, 1],
         q_rank=6, kv_rank=4, d_nope=3, d_rope=2, d_v=5, index_heads=2, index_dim=4)
PEAKS = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e5}
WORK = {"decode_steps": 10, "active_slot_steps": 20, "moe_pairs_held": 7,
        "moe_experts_touched": 5, "dsa_keys_scanned": 300, "dsa_keys_selected": 80}
NEW = ("glm5_decode_step_mfu", "glm5_decode_step_hbm_roofline", "dsa_index_device_ms_per_step",
       "mla_attn_device_ms_per_step", "mla_attn_roofline", "moe_device_ms_per_step",
       "moe_experts_roofline", "moe_pairs_held_per_token", "moe_expert_load_max_over_mean",
       "dsa_selected_share")


def context(work=WORK, program=M, modules=None):
    cell = type("Cell", (), {"name": "made", "chips": 1, "config": {"program": program}})()
    modules = {"jit_decode": {"seconds": 0.5, "count": 10},
               "jit_prefill": {"seconds": 9.0, "count": 1}} if modules is None else modules
    return {"cell": cell, "peaks": PEAKS, "trace": {"modules": modules, "ops": {}},
            "segment": {"seconds": 1.0, "work": dict(work)}, "counters": {}}


def test_counts_by_hand():
    # attention: q_a 8*6 + q_b 6*2*5 + kv_a 8*6 + kv_b 4*2*8 + o 2*5*8 = 300
    # indexer:   6*2*4 + 8*4 + 8*2 = 96
    assert share.kv_b_params(M) == 64 and share.expert_params(M) == 96
    assert share.layer_matrix_params(M, dense=True) == 300 + 96 + 3 * 8 * 12
    assert share.layer_matrix_params(M, dense=False) == 300 + 96 + 8 * 16 + 96
    per_token = 684 + 2 * 620 + 10 * 8
    assert share.token_matrix_params(M) == per_token == 2004
    assert share.index_flops(M, 300) == 2 * 2 * 4 * 300
    assert share.attn_core_flops(M, 80) == 2 * 2 * (2 * 4 + 2) * 80
    assert share.step_flops(M, WORK) == 2 * 2004 * 20 + 2 * 96 * 7 + 4800 + 3200
    assert share.step_bytes(M, WORK) == 2 * (2004 * 10 + 96 * 5 + 4 * 300 + 6 * 80)
    assert share.attn_cost(M, WORK) == (2 * 64 * 3 * 20 + 3200, 2 * (64 * 30 + 6 * 80))
    assert share.experts_cost(M, WORK) == (2 * 96 * 7, 2 * 96 * 5)


def test_step_shares_by_hand():
    ctx = context()
    flops = {"of": "step_flops", "match": "^jit_decode$"}
    bytes_ = {"of": "step_bytes", "match": "^jit_decode$"}
    # 89504 operations are 0.089504 s at the peak, of 0.5 s on the device;
    # 44400 bytes are 0.444 s at the peak: 88.8 %, and nothing is clipped
    assert share.read(ctx, flops) == pytest.approx(100 * 0.089504 / 0.5)
    assert share.read(ctx, bytes_) == pytest.approx(88.8)
    fast = context(modules={"jit_decode": {"seconds": 0.25, "count": 10}})
    assert share.read(fast, bytes_) == pytest.approx(177.6)


def test_scope_shares_by_hand(monkeypatch):
    monkeypatch.setattr(share, "scope_seconds",
                        lambda ctx, scope: {"mx.gen.attn": 0.1, "mx.lm.moe.experts": 0.05}[scope])
    ctx = context()
    # attention: 10880 operations (0.01088 s) against 4800 bytes (0.048 s)
    assert share.read(ctx, {"of": "attn", "scope": "mx.gen.attn"}) == pytest.approx(48.0)
    # experts: 1344 operations against 960 bytes (0.0096 s) in 0.05 s
    assert share.read(ctx, {"of": "experts", "scope": "mx.lm.moe.experts"}) \
        == pytest.approx(19.2)


def parents_contexts():
    """What a traced run of a program without any of this hands the readers:
    the transformer's configuration, its runner's counters, a trace with its
    own programs; and a run that was not traced at all."""
    opt = {"vocab": 100, "d_model": 8, "n_heads": 2, "n_layers": 2, "d_ff": 16}
    base = {"decode_steps": 10, "slot_steps": 160, "active_slot_steps": 20, "tokens": 25,
            "prefills": 1, "requests": 3}
    yield context(work=base, program=opt)
    yield context(work=WORK, program=opt)                  # counters, another model
    yield context(work=base)                               # the model, no counters
    yield dict(context(work=base, program=opt), trace=None, segment=None, peaks=None)


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_returns_nothing_on_a_parents_run(name):
    from mxnet_tpu import profiler

    profiler.generate_reset()
    spec = harness.load_json(os.path.join(harness.HERE, "metrics", name + ".json"))
    reader = harness.load_module("readers", spec["reader"])
    for ctx in parents_contexts():
        work = (ctx["segment"] or {}).get("work", {})
        if name in ("moe_pairs_held_per_token", "dsa_selected_share") and "moe_pairs_held" in work:
            continue        # a plain ratio of two counters the runner handed on
        assert reader.read(ctx, spec.get("args", {})) is None, name


def test_new_metrics_list_the_new_cell_alone():
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert sorted(m["name"] for m in mine) == sorted(NEW)
    assert bench["per_layer"][-len(NEW):] == mine          # appended, nothing between
    for m in mine:
        assert m["workloads"] == ["glm-5.decode-pool-16k"]
        assert m["moves"] == "serve_itl_p95_ms"
    for m in bench["per_layer"]:
        if "glm-5.decode-pool-16k" in m.get("workloads", []) and m["name"] not in NEW:
            assert m["workloads"][-1] == "glm-5.decode-pool-16k" and len(m["workloads"]) == 2
    for name in ("serve_decode_step_mfu", "serve_decode_step_hbm_roofline",
                 "serve_decode_kv_read_share", "serve_ttft_p95_host_ms"):
        entry = [m for m in bench["per_layer"] if m["name"] == name][0]
        assert entry["workloads"] == ["opt-1.3b.serve-chat"]
