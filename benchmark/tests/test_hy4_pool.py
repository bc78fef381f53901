"""The Hy4-preview decode-pool cell at rehearsal size on the CPU (a sound run
is correct and counts a reused selection for each shared layer of every
active slot; the int8 control and planted faults in the program are not
correct), its reader's counts by hand and its silence on a parent's counters,
its metrics' listing, and its configuration against the catalog row."""
import json
import os

import pytest

from benchmark import harness, traffic_gen
from benchmark.proof import decode_pool_readings as readings
from benchmark.proof import hy4_pool
from benchmark.readers import glm5_step_share as glm
from benchmark.readers import hy4_step_share as share
from benchmark.runners import serve_decode_pool_hy4 as runner
from benchmark.tests import test_benchmark_json as contract

SEED = 2 ** 31 + 4242
CATALOG = os.environ.get("MODEL_CATALOG", "")  # a JSON-lines file of published configs
M = dict(vocab=10, d_model=8, n_heads=2, n_layers=5, n_dense_layers=1, d_ff=12, d_expert=4,
         n_experts=16, experts_per_token=2, held_experts=[0, 1], q_rank=6, kv_rank=4,
         d_nope=3, d_rope=2, d_v=5, index_heads=2, index_dim=4,
         indexer_types=["full", "full", "shared", "shared", "shared"], hc_mult=4)
PEAKS = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e5}
WORK = {"decode_steps": 10, "active_slot_steps": 20, "moe_pairs_held": 7,
        "moe_experts_touched": 5, "moe_tokens": 80, "dsa_keys_scanned": 300,
        "dsa_keys_selected": 400, "dsa_selections_reused": 60}
NEW = ("hc_device_ms_per_step", "hy4_decode_step_hbm_roofline", "hy4_decode_step_mfu")
LISTED = ("serve_itl_p95_ms", "serve_decode_step_device_ms", "device_idle_share.serve",
          "moe_pairs_held_per_token", "moe_expert_load_max_over_mean",
          "moe_device_ms_per_step", "dsa_index_device_ms_per_step",
          "mla_attn_device_ms_per_step", "mla_attn_roofline", "moe_experts_roofline")


def context(work=WORK, program=M):
    cell = type("Cell", (), {"name": "made", "chips": 1, "config": {"program": program}})()
    modules = {"jit_decode": {"seconds": 0.5, "count": 10}}
    return {"cell": cell, "peaks": PEAKS, "trace": {"modules": modules, "ops": {}},
            "segment": {"seconds": 1.0, "work": dict(work)}, "counters": {}}


def rehearsed_run(run_cls=None):
    mod = hy4_pool.rehearsal()
    cell = harness.Cell(mod.BENCH, mod.CELL)
    cls = run_cls or harness.load_module("runners", cell.traffic["runner"]).Run
    run = cls(cell, harness.require_devices(1, True), SEED, harness.Tracer(False, cell.name))
    run.setup()
    return mod, run


def test_sound_run_is_correct_and_its_control_is_not(capsys):
    import mxnet_tpu  # noqa: F401

    mod = hy4_pool.rehearsal()
    mod.drive(["--seed", str(SEED), "--seconds", "0.3", "--trace", "0"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 4 and result["compared"]
    _mod, run = rehearsed_run()
    mix = run.mix
    assert run.srv.predictor.pool.num_pages == runner.pool_pages(
        [len(r["prompt"]) for r in run.requests], mix["answer_tokens"], mix["page_size"],
        mix["slots"], mix["pool_margin"])
    _got, exact, control, below = readings.readings(run, 0.3)
    # the three shared layers of every active slot-step reused a selection
    work = run._counters()
    assert work["active_slot_steps"] > 0
    assert work["dsa_selections_reused"] == 3 * work["active_slot_steps"]
    assert below == "int8" and len(exact) >= 800
    assert readings.judged(run, exact)
    assert not readings.judged(run, control)


@pytest.mark.parametrize("fault", hy4_pool.PLANTS)
def test_planted_faults_are_not_correct(fault, monkeypatch):
    """The served program with the shared layers choosing their own keys, or
    without its sinks (the reference keeps both): the comparison must fail."""
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.models import mla_moe as mm

    run_cls = None
    if fault == "sink_dropped":
        monkeypatch.setattr(mm, "_layer", mm._layer)      # undone after the test
        hy4_pool.drop_sinks()
    else:
        run_cls = hy4_pool.with_own_selections(runner.Run)
    _mod, run = rehearsed_run(run_cls)
    if fault == "own_selections":
        pred = run.srv.predictor
        assert len(pred._kv["index"]) == 5 and "dsa_selections_reused" not in \
            pred._counter_names
    _got, exact, _control, _below = readings.readings(run, 0.3, control=False)
    assert not readings.judged(run, exact), fault


def test_pool_pages_of_the_cell():
    mix = harness.load_json(os.path.join(harness.HERE, "traffic", "decode-pool-32k.json"))
    prompts = [len(r["prompt"]) for r in traffic_gen.open_loop_requests(
        dict(mix, rate_per_s=1.0, answer_tokens={"median": 1, "sigma": 0.0, "min": 1,
                                                 "max": 1}), 10, SEED, 16)]
    pages = runner.pool_pages(prompts, mix["answer_tokens"], mix["page_size"],
                              mix["slots"], mix["pool_margin"])
    assert sum(prompts) == 343091 and min(prompts) == 12288 and max(prompts) == 32768
    assert mix["max_ctx"] == 32768 + mix["answer_tokens"]
    assert pages < 0.9 * mix["slots"] * (mix["max_ctx"] // mix["page_size"])


def test_counts_by_hand():
    # what a layer adds: the gate 8*2*5 and two projections of 32 x 24
    assert share.added_layer_params(M) == 80 + 2 * 32 * 24 == 1616
    # the indexer: W_Iq 6*2*4 + W_Ik 8*4 + W_Iw 8*2
    assert share.index_params(M) == 96
    glm_params = glm.token_matrix_params(M)
    assert share.token_matrix_params(M) == glm_params + 5 * 1616 - 3 * 96
    # streams: 10 sublayers of 2*16*8 + 4*4*8
    assert share.stream_flops(M) == 10 * (256 + 128)
    per_token = share.token_matrix_params(M)
    assert share.step_flops(M, WORK) == (
        2 * (per_token + 2 * 80) * 20 + 3840 * 20 + 2 * 96 * 7
        + 2 * 2 * 4 * 300 + 2 * 2 * (8 + 2) * 400)
    assert share.step_bytes(M, WORK) == 2 * (per_token * 10 + 96 * 5 + 4 * 300 + 6 * 400)


def test_shares_by_hand():
    ctx = context()
    want = share.step_bytes(M, WORK) / 1e5 / 0.5 * 100
    assert share.read(ctx, {"of": "step_bytes", "match": "^jit_decode$"}) \
        == pytest.approx(want)
    want = share.step_flops(M, WORK) / 1e6 / 0.5 * 100
    assert share.read(ctx, {"of": "step_flops", "match": "^jit_decode$"}) \
        == pytest.approx(want)


@pytest.mark.parametrize("of", ["step_bytes", "step_flops"])
def test_the_reader_is_silent_on_a_parent_and_on_other_kinds(of):
    """A parent cannot run this cell and counts no reuse; GLM-5's program has
    no streams."""
    args = {"of": of, "match": "^jit_decode$"}
    parent = {k: v for k, v in WORK.items() if k != "dsa_selections_reused"}
    assert share.read(context(work=parent), args) is None
    assert share.read(context(program={k: v for k, v in M.items() if k != "hc_mult"}),
                      args) is None
    assert share.read(context(), args) is not None


def test_new_metrics_are_appended_and_list_the_new_cell_alone():
    bench = contract.load("BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(n) for n in NEW]
    assert at == list(range(at[0], at[0] + len(NEW)))    # together, in this order
    assert at[0] > names.index("sarvam_decode_step_mfu")  # after what was there
    readers = {"hc_device_ms_per_step": "scope_device_ms_per_work"}
    for name in NEW:
        m = bench["per_layer"][names.index(name)]
        assert m["workloads"] == [hy4_pool.CELL] and m["moves"] == "serve_itl_p95_ms"
        assert harness.load_json(os.path.join(harness.HERE, "metrics", name + ".json"))[
            "reader"] == readers.get(name, "hy4_step_share")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in LISTED:
            assert hy4_pool.CELL in m["workloads"], m["name"]
            assert m["workloads"].index(hy4_pool.CELL) > m["workloads"].index(
                "glm-5.decode-pool-16k"), m["name"]
        elif m["name"] == "dsa_selected_share":
            assert hy4_pool.CELL not in m["workloads"]
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(hy4_pool.CELL) > cells.index("sarvam-105b.decode-pool-64k")
    assert bench["workloads"][cells.index(hy4_pool.CELL)]["chips"] == 1


@pytest.fixture(scope="module")
def bench():
    return contract.load("benchmark/rehearse/decode-pool-hy4/BENCHMARK.json")


@pytest.mark.parametrize("check", [contract.test_top_level, contract.test_configs,
                                   contract.test_workloads, contract.test_metrics],
                         ids=lambda f: f.__name__)
def test_rehearsal_benchmark_keeps_the_contract(check, bench):
    check(bench)


def test_the_configuration_is_the_catalog_row_cut_in_depth_experts_and_vocabulary():
    if not os.path.isfile(CATALOG):
        pytest.skip("no model catalog named by MODEL_CATALOG")
    row = [json.loads(x) for x in open(CATALOG) if '"Hy4-preview"' in x][0]
    top = contract.load("BENCHMARK.json")
    entry = [c for c in top["configs"] if c["name"] == "hy4-preview-ep32"][0]
    held = contract.load(entry["file"])
    assert entry["source"] == held["source"] == row["source_url"]
    assert held["published"] == row["config"]
    assert sorted(entry["reduced"]) == sorted(held["reduced"])
    for key, value in row["config"].items():
        if key not in entry["reduced"]:
            assert held[key] == value, key
    for key in ("stands_for", "assumed", "departures", "deployment", "program", "reference"):
        assert held[key], key
    p, pub = held["program"], held["published"]
    assert (p["d_model"], p["n_heads"], p["q_rank"], p["kv_rank"], p["d_nope"], p["d_rope"],
            p["d_v"], p["d_ff"], p["d_expert"], p["n_experts"], p["experts_per_token"],
            p["route_scale"], p["rope_theta"], p["norm_eps"], p["index_heads"],
            p["index_dim"], p["index_topk"], p["hc_mult"], p["hc_magnitude"], p["hc_eps"],
            p["swiglu_limit"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["q_lora_rank"],
        pub["kv_lora_rank"], pub["qk_nope_head_dim"], pub["qk_rope_head_dim"],
        pub["v_head_dim"], pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["n_routed_experts"], pub["num_experts_per_tok"], pub["routed_scaling_factor"],
        pub["rope_parameters"]["rope_theta"], pub["rms_norm_eps"], pub["index_n_heads"],
        pub["index_head_dim"], pub["index_topk"], pub["hc_mult"], pub["hc_magnitude"],
        pub["hc_eps"], pub["swiglu_limit"])
    assert p["attn_gate"] is pub["gated_mla"] is True
    assert p["attn_sink"] is pub["learnable_sink"] is True
    assert p["head_fp32"] is pub["enable_lm_head_fp32"] is True
    assert p["indexer_types"] == pub["indexer_types"][:5] == held["indexer_types"]
    assert p["held_experts"] == held["deployment"]["held_expert_ids"] == list(range(8))
    assert len(p["held_experts"]) == held["n_routed_experts"] >= 8
    assert p["vocab"] == held["vocab_size"] == pub["vocab_size"] // 8
    assert p["n_layers"] == held["num_hidden_layers"] == 5
