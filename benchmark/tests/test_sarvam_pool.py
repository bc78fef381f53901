"""The Sarvam-105B decode-pool cell at rehearsal size on the CPU (a sound run
is correct; the int8 control and planted faults in the program are not), its
reader's counts by hand and its silence on a parent's counters, its metrics'
listing, and its configuration against the catalog row."""
import json
import os

import pytest

from benchmark import harness, traffic_gen
from benchmark.proof import decode_pool_readings as readings
from benchmark.proof import sarvam_pool
from benchmark.readers import sarvam_step_share as share
from benchmark.runners import serve_decode_pool_sarvam as runner
from benchmark.tests import test_benchmark_json as contract

SEED = 2 ** 31 + 4242
CATALOG = os.environ.get("MODEL_CATALOG", "")  # a JSON-lines file of published configs
M = dict(vocab=10, d_model=8, n_heads=2, n_layers=3, n_dense_layers=1, d_ff=12, d_expert=4,
         n_experts=16, experts_per_token=2, held_experts=[0, 1], q_rank=0, kv_rank=4,
         d_nope=3, d_rope=2, d_v=5, indexer=False)
PEAKS = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e5}
WORK = {"decode_steps": 10, "active_slot_steps": 20, "moe_pairs_held": 7,
        "moe_experts_touched": 5, "moe_tokens": 40, "attn_rows_read": 300}
NEW = ("sarvam_decode_step_hbm_roofline", "sarvam_mla_attn_roofline", "sarvam_experts_roofline",
       "sarvam_decode_step_mfu")
LISTED = ("serve_itl_p95_ms", "serve_decode_step_device_ms", "device_idle_share.serve",
          "moe_pairs_held_per_token", "moe_expert_load_max_over_mean",
          "mla_dense_attn_device_ms_per_step", "moe_device_ms_per_step")


def context(work=WORK, program=M):
    cell = type("Cell", (), {"name": "made", "chips": 1, "config": {"program": program}})()
    modules = {"jit_decode": {"seconds": 0.5, "count": 10}}
    return {"cell": cell, "peaks": PEAKS, "trace": {"modules": modules, "ops": {}},
            "segment": {"seconds": 1.0, "work": dict(work)}, "counters": {}}


def rehearsed_run():
    mod = sarvam_pool.rehearsal()
    cell = harness.Cell(mod.BENCH, mod.CELL)
    run = harness.load_module("runners", cell.traffic["runner"]).Run(
        cell, harness.require_devices(1, True), SEED, harness.Tracer(False, cell.name))
    run.setup()
    return mod, run


def test_sound_run_is_correct_and_its_control_is_not(capsys):
    import mxnet_tpu  # noqa: F401

    mod = sarvam_pool.rehearsal()
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
    assert below == "int8" and len(exact) >= 800
    assert readings.judged(run, exact)
    assert not readings.judged(run, control)


def _plain_rotary(mm):
    return lambda x, positions, c: mm._rope(x, positions, c.rope_theta)


@pytest.mark.parametrize("fault", ["yarn_dropped", "mscale_dropped", "k_norm_dropped"])
def test_planted_faults_are_not_correct(fault, monkeypatch):
    """The served program with a piece of the mathematics left out (the
    reference keeps it): the comparison must fail."""
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.models import mla_moe as mm

    if fault == "yarn_dropped":
        monkeypatch.setattr(mm, "_rotary", _plain_rotary(mm))
    elif fault == "mscale_dropped":
        monkeypatch.setattr(mm, "_softmax_scale", lambda c: (c.d_nope + c.d_rope) ** -0.5)
    else:
        norm = mm._rmsnorm
        monkeypatch.setattr(mm, "_rmsnorm", lambda x, g, eps: x.astype("float32")
                            if g.shape[-1] == 8 and x.shape[-1] == 8 and x.ndim == 2
                            else norm(x, g, eps))
    _mod, run = rehearsed_run()
    _got, exact, _control, _below = readings.readings(run, 0.3, control=False)
    assert not readings.judged(run, exact), fault


def test_pool_pages_by_hand():
    # (14 + 10) / 8 -> 3 pages, (43 + 10) / 8 -> 7, a page a slot for 2 slots:
    # 12, and a tenth more rounded up: 14
    assert runner.pool_pages([14, 43], 10, 8, 2, 0.1) == 14
    # the cell's own: every prompt and answer of the 16 streams in 64-row
    # pages is 61 % of 16 slots of max_ctx
    mix = harness.load_json(os.path.join(harness.HERE, "traffic", "decode-pool-64k.json"))
    prompts = [len(r["prompt"]) for r in traffic_gen.open_loop_requests(
        dict(mix, rate_per_s=1.0, answer_tokens={"median": 1, "sigma": 0.0, "min": 1,
                                                 "max": 1}), 10, SEED, 16)]
    pages = runner.pool_pages(prompts, mix["answer_tokens"], mix["page_size"],
                              mix["slots"], mix["pool_margin"])
    assert sum(prompts) == 561542
    assert pages < 0.65 * mix["slots"] * (mix["max_ctx"] // mix["page_size"])


def test_counts_by_hand():
    # an attention: W_q 8*2*5 + W_kva 8*6 + W_kvb 4*2*8 + W_o 2*5*8 = 272
    assert share.kv_b_params(M) == 64 and share.attn_matrix_params(M) == 272
    # 3 attentions, one dense FFN (3*8*12), two routers (8*16) and shared
    # experts (3*8*4), the head (10*8)
    per_token = 3 * 272 + 288 + 2 * (128 + 96) + 80
    assert share.token_matrix_params(M) == per_token == 1632
    assert share.step_bytes(M, WORK) == 2 * (1632 * 10 + 96 * 5) + 12 * 300
    assert share.attn_cost(M, WORK) == (2 * 64 * 3 * 20 + 2 * 2 * 10 * 300,
                                        2 * 64 * 3 * 10 + 12 * 300)
    # the tokens' matrices, 7 pairs on held experts of 96, the core a row
    assert share.step_flops(M, WORK) == 2 * 1632 * 20 + 2 * 96 * 7 + 2 * 2 * 10 * 300
    assert share.experts_cost(M, WORK) == (2 * 96 * 7, 2 * 96 * 5)


def test_shares_by_hand(monkeypatch):
    ctx = context()
    # 37200 bytes are 0.372 s at the peak of 0.5 s on the device: 74.4 %
    assert share.read(ctx, {"of": "step_bytes", "match": "^jit_decode$"}) \
        == pytest.approx(74.4)
    monkeypatch.setattr(share, "scope_seconds", lambda ctx, scope: 0.1)
    # 19680 operations (0.01968 s) against 7440 bytes (0.0744 s) over 0.1 s
    assert share.read(ctx, {"of": "attn", "scope": "mx.gen.attn"}) == pytest.approx(74.4)
    # 1344 operations (0.001344 s) against 960 bytes (0.0096 s) over 0.1 s
    assert share.read(ctx, {"of": "experts", "scope": "mx.lm.moe.experts"}) \
        == pytest.approx(9.6)
    # 78624 operations are 0.078624 s at the peak of 0.5 s on the device
    assert share.read(ctx, {"of": "step_flops", "match": "^jit_decode$"}) \
        == pytest.approx(15.7248)


@pytest.mark.parametrize("of", ["step_bytes", "attn", "experts", "step_flops"])
def test_the_reader_is_silent_on_a_parent_and_on_other_kinds(of, monkeypatch):
    """The parent's programs count no ``attn_rows_read`` for this cell (it
    cannot run it); GLM-5's and LongCat-Flash's programs are other kinds."""
    monkeypatch.setattr(share, "scope_seconds", lambda ctx, scope: 0.1)
    args = {"of": of, "match": "^jit_decode$", "scope": "mx.gen.attn"}
    parent = {k: v for k, v in WORK.items() if k != "attn_rows_read"}
    assert share.read(context(work=parent), args) is None
    assert share.read(context(program=dict(M, q_rank=6)), args) is None
    assert share.read(context(program=dict(M, indexer=True)), args) is None
    assert share.read(context(program={k: v for k, v in M.items() if k != "indexer"}),
                      args) is None
    assert share.read(context(), args) is not None


def test_new_metrics_are_appended_and_list_the_new_cell_alone():
    bench = contract.load("BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(n) for n in NEW]
    assert at == list(range(at[0], at[0] + len(NEW)))    # together, in this order
    assert at[0] > names.index("serve_gc_ms_per_step")   # after what was there
    for name in NEW:
        m = bench["per_layer"][names.index(name)]
        assert m["workloads"] == [sarvam_pool.CELL] and m["moves"] == "serve_itl_p95_ms"
        assert harness.load_json(os.path.join(harness.HERE, "metrics", name + ".json"))[
            "reader"] == "sarvam_step_share"
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in LISTED:
            assert m["workloads"][-1] == sarvam_pool.CELL, m["name"]
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(sarvam_pool.CELL) > cells.index("longcat-flash.decode-pool-12k")
    assert bench["workloads"][cells.index(sarvam_pool.CELL)]["chips"] == 1


@pytest.fixture(scope="module")
def bench():
    return contract.load("benchmark/rehearse/decode-pool-sarvam/BENCHMARK.json")


@pytest.mark.parametrize("check", [contract.test_top_level, contract.test_configs,
                                   contract.test_workloads, contract.test_metrics],
                         ids=lambda f: f.__name__)
def test_rehearsal_benchmark_keeps_the_contract(check, bench):
    check(bench)


def test_the_configuration_is_the_catalog_row_cut_in_depth_experts_and_vocabulary():
    if not os.path.isfile(CATALOG):
        pytest.skip("no model catalog named by MODEL_CATALOG")
    row = [json.loads(x) for x in open(CATALOG) if '"sarvam-105b"' in x][0]
    top = contract.load("BENCHMARK.json")
    entry = [c for c in top["configs"] if c["name"] == "sarvam-105b-ep16"][0]
    held = contract.load(entry["file"])
    assert entry["source"] == held["source"] == row["source_url"]
    assert held["published"] == row["config"]
    assert sorted(entry["reduced"]) == sorted(held["reduced"]) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size"])
    for key, value in row["config"].items():
        if key not in entry["reduced"]:
            assert held[key] == value, key
    for key in ("stands_for", "assumed", "departures", "deployment", "program", "reference"):
        assert held[key], key
    for key in ("q_norm", "k_norm", "router", "router_bias_std", "yarn"):
        assert held["assumed"][key], key
    p, pub = held["program"], held["published"]
    yarn = pub["rope_scaling"]
    assert (p["d_model"], p["n_heads"], p["kv_rank"], p["d_nope"], p["d_rope"], p["d_v"],
            p["d_ff"], p["d_expert"], p["n_experts"], p["experts_per_token"],
            p["route_scale"], p["rope_theta"], p["norm_eps"], p["n_dense_layers"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["kv_lora_rank"],
        pub["qk_nope_head_dim"], pub["qk_rope_head_dim"], pub["v_head_dim"],
        pub["intermediate_size"], pub["moe_intermediate_size"], pub["num_experts"],
        pub["num_experts_per_tok"], pub["routed_scaling_factor"], pub["rope_theta"],
        pub["rms_norm_eps"], pub["first_k_dense_replace"])
    assert p["d_nope"] + p["d_rope"] == pub["q_head_dim"]
    assert p["kv_rank"] + p["d_rope"] == pub["head_dim"]
    from benchmark.reference import sarvam_lm
    from mxnet_tpu.models import mla_moe

    assert yarn["type"] == "deepseek_yarn"
    assert (p["yarn_factor"], p["yarn_original"]) == (
        yarn["factor"], yarn["original_max_position_embeddings"])
    # the rest of rope_scaling is the program's and the reference's constants
    assert (yarn["beta_fast"], yarn["beta_slow"]) == (
        mla_moe.YARN_BETA_FAST, mla_moe.YARN_BETA_SLOW) == (
        sarvam_lm.BETA_FAST, sarvam_lm.BETA_SLOW)
    assert (yarn["mscale"], yarn["mscale_all_dim"]) == (
        sarvam_lm.MSCALE, sarvam_lm.MSCALE_ALL_DIM) == (1, 1)
    assert p["q_rank"] == 0 and p["indexer"] is False
    assert p["qk_norm"] is pub["use_qk_norm"] is True
    assert p["held_experts"] == held["deployment"]["held_expert_ids"] == list(range(8))
    assert len(p["held_experts"]) == held["num_experts"] >= 8
    assert p["vocab"] == held["vocab_size"] == pub["vocab_size"] // 8
    assert p["n_layers"] == held["num_hidden_layers"] == 5
