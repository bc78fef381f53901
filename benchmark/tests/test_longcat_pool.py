"""ISSUE 38's cell at rehearsal size on the CPU, its reader's counts by hand,
its configuration against the catalog row, and every metric file's reader on a
context with no trace, no scope and no counters (a parent's run): nothing."""
import glob
import json
import os

import pytest

from benchmark import harness
from benchmark.proof import decode_pool_readings as readings
from benchmark.proof import longcat_pool
from benchmark.readers import longcat_step_share as share
from benchmark.tests import test_benchmark_json as contract

SEED = 2 ** 31 + 4242
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
M = dict(vocab=10, d_model=8, n_heads=2, n_layers=3, d_ff=12, d_expert=4, n_experts=16,
         n_zero_experts=8, experts_per_token=2, held_experts=[0, 1], q_rank=6, kv_rank=4,
         d_nope=3, d_rope=2, d_v=5)
PEAKS = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e5}
WORK = {"decode_steps": 10, "active_slot_steps": 20, "moe_pairs_held": 7,
        "moe_experts_touched": 5, "moe_pairs_zero": 40, "moe_tokens": 60,
        "attn_rows_read": 300}
NEW = ("longcat_decode_step_mfu", "longcat_decode_step_hbm_roofline",
       "mla_dense_attn_device_ms_per_step", "mla_dense_attn_roofline",
       "scmoe_dense_ffn_device_ms_per_step", "scmoe_moe_device_ms_per_step",
       "scmoe_experts_roofline", "moe_zero_pair_share")


def context(work=WORK, program=M):
    cell = type("Cell", (), {"name": "made", "chips": 1, "config": {"program": program}})()
    modules = {"jit_decode": {"seconds": 0.5, "count": 10}}
    return {"cell": cell, "peaks": PEAKS, "trace": {"modules": modules, "ops": {}},
            "segment": {"seconds": 1.0, "work": dict(work)}, "counters": {}}


def test_sound_run_is_correct_and_its_control_is_not(capsys):
    import mxnet_tpu  # noqa: F401

    mod = longcat_pool.rehearsal()
    mod.drive(["--seed", str(SEED), "--seconds", "0.3", "--trace", "0"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 4 and result["compared"]
    cell = harness.Cell(mod.BENCH, mod.CELL)
    runner = harness.load_module("runners", cell.traffic["runner"])
    run = runner.Run(cell, harness.require_devices(1, True), SEED,
                     harness.Tracer(False, cell.name))
    run.setup()
    _got, exact, control, below = readings.readings(run, 0.3)
    assert below == "int8" and len(exact) >= 40
    assert readings.judged(run, exact)
    assert not readings.judged(run, control)


def test_counts_by_hand():
    # an attention: q_a 8*6 + q_b 6*2*5 + kv_a 8*6 + kv_b 4*2*8 + o 2*5*8 = 300
    assert share.kv_b_params(M) == 64 and share.expert_params(M) == 96
    assert share.sublayer_matrix_params(M) == 300 + 3 * 8 * 12
    per_token = 3 * (2 * 588 + 8 * 24) + 10 * 8
    assert share.token_matrix_params(M) == per_token == 4184
    assert share.attn_core_flops(M, 300) == 2 * 2 * (2 * 4 + 2) * 300 == 12000
    assert share.step_flops(M, WORK) == 2 * 4184 * 20 + 2 * 96 * 7 + 12000
    assert share.step_bytes(M, WORK) == 2 * (4184 * 10 + 96 * 5) + 12 * 300
    assert share.attn_cost(M, WORK) == (2 * 64 * 6 * 20 + 12000, 2 * 64 * 6 * 10 + 12 * 300)
    assert share.experts_cost(M, WORK) == (2 * 96 * 7, 2 * 96 * 5)


def test_shares_by_hand(monkeypatch):
    ctx = context()
    # 180704 operations are 0.180704 s at the peak of 0.5 s on the device;
    # 88240 bytes are 0.8824 s: 176.48 %, and nothing is clipped
    assert share.read(ctx, {"of": "step_flops", "match": "^jit_decode$"}) \
        == pytest.approx(100 * 0.180704 / 0.5)
    assert share.read(ctx, {"of": "step_bytes", "match": "^jit_decode$"}) \
        == pytest.approx(176.48)
    monkeypatch.setattr(share, "scope_seconds",
                        lambda ctx, scope: {"mx.gen.attn": 0.2, "mx.lm.moe.experts": 0.05}[scope])
    # attention: 27360 operations (0.02736 s) against 11280 bytes (0.1128 s)
    assert share.read(ctx, {"of": "attn", "scope": "mx.gen.attn"}) == pytest.approx(56.4)
    assert share.read(ctx, {"of": "experts", "scope": "mx.lm.moe.experts"}) \
        == pytest.approx(19.2)
    zero = harness.load_json(os.path.join(harness.HERE, "metrics", "moe_zero_pair_share.json"))
    reader = harness.load_module("readers", zero["reader"])
    # 40 of 60 x 12 choices (the cell's top-12): 5.56 %
    assert reader.read(ctx, zero["args"]) == pytest.approx(100 * 40 / (12 * 60))


def empty_contexts():
    """A run that was not traced, and a traced run of a program that has none
    of the scopes or counters: the transformer's configuration, its runner's
    counters, a trace that holds another program."""
    opt = {"vocab": 100, "d_model": 8, "n_heads": 2, "n_layers": 2, "d_ff": 16}
    base = {"decode_steps": 10, "slot_steps": 160, "active_slot_steps": 20, "tokens": 25,
            "prefills": 1, "requests": 3}
    cell = type("Cell", (), {"name": "made", "chips": 1, "config": {"program": opt}})()
    yield {"cell": cell, "peaks": None, "trace": None, "segment": None, "counters": {}}
    yield {"cell": cell, "peaks": PEAKS, "trace": {"modules": {}, "ops": {}, "busy_s": 0.0,
                                                  "window_s": 1.0},
           "segment": {"seconds": 1.0, "work": base}, "counters": {}}


SPECS = sorted(glob.glob(os.path.join(harness.HERE, "metrics", "*.json")))


@pytest.mark.parametrize("path", SPECS, ids=lambda p: os.path.basename(p)[:-5])
def test_every_reader_returns_nothing_where_there_is_nothing_to_read(path):
    """Every metric file's reader on a run with no trace, no scope and no
    counters; this PR's also on a traced run of a program without theirs."""
    from mxnet_tpu import profiler

    profiler.generate_reset()
    spec = harness.load_json(path)
    reader = harness.load_module("readers", spec["reader"])
    name = os.path.basename(path)[:-5]
    untraced, another_program = empty_contexts()
    assert reader.read(untraced, spec.get("args", {})) is None, name
    if name in NEW:
        assert reader.read(another_program, spec.get("args", {})) is None, name


def test_new_metrics_are_appended_and_list_the_new_cell_alone():
    bench = contract.load("BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW)
    assert bench["per_layer"][-len(NEW):] == mine          # appended, nothing between
    for m in mine:
        assert m["workloads"] == [longcat_pool.CELL] and m["moves"] == "serve_itl_p95_ms"
    for m in bench["end_to_end"] + bench["per_layer"]:
        if longcat_pool.CELL in m.get("workloads", []) and m["name"] not in NEW:
            assert m["workloads"][-1] == longcat_pool.CELL
    assert bench["workloads"][-1]["name"] == longcat_pool.CELL
    assert bench["workloads"][-1]["chips"] == 1


@pytest.fixture(scope="module")
def bench():
    return contract.load("benchmark/rehearse/decode-pool-longcat/BENCHMARK.json")


@pytest.mark.parametrize("check", [contract.test_top_level, contract.test_configs,
                                   contract.test_workloads, contract.test_metrics],
                         ids=lambda f: f.__name__)
def test_rehearsal_benchmark_keeps_the_contract(check, bench):
    check(bench)


def test_the_configuration_is_the_catalog_row_cut_in_depth_experts_and_vocabulary():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    row = [json.loads(x) for x in open(CATALOG) if '"LongCat-Flash-Chat"' in x][0]
    top = contract.load("BENCHMARK.json")
    entry = [c for c in top["configs"] if c["name"] == "longcat-flash-chat-ep32"][0]
    held = contract.load(entry["file"])
    assert entry["source"] == held["source"] == row["source_url"]
    assert held["published"] == row["config"]
    assert sorted(entry["reduced"]) == sorted(held["reduced"]) == sorted(
        ["num_layers", "n_routed_experts", "vocab_size"])
    for key, value in row["config"].items():
        if key not in entry["reduced"]:
            assert held[key] == value, key
    for key in ("stands_for", "assumed", "departures", "deployment", "program", "reference"):
        assert held[key], key
    p, pub = held["program"], held["published"]
    assert (p["d_model"], p["n_heads"], p["q_rank"], p["kv_rank"], p["d_nope"], p["d_rope"],
            p["d_v"], p["d_ff"], p["d_expert"], p["n_experts"], p["n_zero_experts"],
            p["experts_per_token"], p["route_scale"], p["rope_theta"], p["norm_eps"],
            p["scale_q_lora"], p["scale_kv_lora"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["q_lora_rank"],
        pub["kv_lora_rank"], pub["qk_nope_head_dim"], pub["qk_rope_head_dim"],
        pub["v_head_dim"], pub["ffn_hidden_size"], pub["expert_ffn_hidden_size"],
        pub["n_routed_experts"], pub["zero_expert_num"], pub["moe_topk"],
        pub["routed_scaling_factor"], pub["rope_theta"], pub["rms_norm_eps"],
        pub["mla_scale_q_lora"], pub["mla_scale_kv_lora"])
    assert p["held_experts"] == held["deployment"]["held_expert_ids"] == list(range(16))
    assert len(p["held_experts"]) == held["n_routed_experts"] >= 8
    assert p["vocab"] == held["vocab_size"] == pub["vocab_size"] // 8
    assert p["n_layers"] == held["num_layers"] == 4
