"""The decode-pool runner at rehearsal size on the CPU: a sound run comes out
correct, its int8 control does not, and neither does a run whose timed path
is broken underneath (a token altered where it is produced, the selection
dropped so that attention reads keys it should not); a stream that ends
inside the window counts as failed.  The cell's files are
those of ``benchmark/rehearse/decode-pool``; its configuration keeps to the
contract as the others do."""
import json
import os

import pytest

from benchmark import harness
from benchmark.proof import decode_pool_readings as readings
from benchmark.proof import rehearse_decode_pool as rehearse
from benchmark.tests import test_benchmark_json as contract

SEED = 2 ** 31 + 4242


def drive(capsys, seconds="0.3"):
    rehearse.drive(["--seed", str(SEED), "--seconds", seconds, "--trace", "0"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    result = drive(capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 4 and result["compared"]
    assert result["metrics"] == {} and result["compiled_in_window"] == 0
    assert list(result)[-1] == "compared"


def test_a_stream_that_ends_inside_the_window_counts_as_failed(capsys):
    """The tiny streams last about a second; a window of four empties every
    slot, and a faster step may not do that to the cell unseen."""
    result = drive(capsys, seconds="4")
    assert result["attempted"] == 4 and result["failed"] == 4


def token_altered(monkeypatch):
    from benchmark.tests.test_correct import alter_read_tokens

    alter_read_tokens(monkeypatch)


def selection_dropped(monkeypatch):
    from mxnet_tpu.models import mla_moe

    monkeypatch.setattr(mla_moe, "_index_scores",
                        readings.zeroed_decode_scores(mla_moe._index_scores))


@pytest.mark.parametrize("plant", [token_altered, selection_dropped])
def test_broken_timed_path_is_not_correct(plant, capsys, monkeypatch):
    plant(monkeypatch)
    result = drive(capsys)
    assert result["correct"] is False
    assert [n for n, c in result["compared"].items() if not c["value"] <= c["limit"]]


def test_control_is_not_correct():
    import mxnet_tpu  # noqa: F401

    cell = harness.Cell(rehearse.BENCH, rehearse.CELL)
    runner = harness.load_module("runners", cell.traffic["runner"])
    run = runner.Run(cell, harness.require_devices(1, True), SEED,
                     harness.Tracer(False, cell.name))
    run.setup()
    _got, exact, control, _below = readings.readings(run, 0.3)
    assert len(exact) >= 40
    assert readings.judged(run, exact)
    assert not readings.judged(run, control)


@pytest.fixture(scope="module")
def bench():
    return contract.load("benchmark/rehearse/decode-pool/BENCHMARK.json")


@pytest.mark.parametrize("check", [contract.test_top_level, contract.test_configs,
                                   contract.test_workloads, contract.test_metrics],
                         ids=lambda f: f.__name__)
def test_rehearsal_benchmark_keeps_the_contract(check, bench):
    check(bench)


def test_the_configuration_cuts_no_width_and_states_its_share():
    top = contract.load("BENCHMARK.json")
    entry = [c for c in top["configs"] if c["name"] == "glm-5-ep16"][0]
    held = contract.load(entry["file"])
    assert sorted(entry["reduced"]) == sorted(held["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size",
         "num_nextn_predict_layers"])
    for key, value in held["published"].items():
        if key not in entry["reduced"]:
            assert held[key] == value, key
    for key in ("source", "stands_for", "assumed", "departures", "deployment", "program",
                "reference"):
        assert held[key], key
    p, pub = held["program"], held["published"]
    assert (p["d_model"], p["n_heads"], p["q_rank"], p["kv_rank"], p["d_nope"], p["d_rope"],
            p["d_v"], p["d_ff"], p["d_expert"], p["index_heads"], p["index_dim"],
            p["index_topk"], p["n_experts"], p["experts_per_token"], p["route_scale"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["q_lora_rank"], pub["kv_lora_rank"],
        pub["qk_nope_head_dim"], pub["qk_rope_head_dim"], pub["v_head_dim"],
        pub["intermediate_size"], pub["moe_intermediate_size"], pub["index_n_heads"],
        pub["index_head_dim"], pub["index_topk"], pub["n_routed_experts"],
        pub["num_experts_per_tok"], pub["routed_scaling_factor"])
    assert p["held_experts"] == held["deployment"]["held_expert_ids"] == list(range(16))
    assert p["vocab"] == held["vocab_size"] == pub["vocab_size"] // 8
    assert p["n_layers"] == held["num_hidden_layers"] and p["n_dense_layers"] == 1
