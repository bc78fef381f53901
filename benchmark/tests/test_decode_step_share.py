"""The decode step's shares of the chip's peaks, on a context made by hand
where both are known, and on the recorded trace, which holds no decode."""
import os

import pytest

from benchmark import harness, trace_reduce
from benchmark.readers import decode_step_share

RECORDED = os.path.join(os.path.dirname(__file__), "data", "span_trace.xplane.pb")
# 2 layers of 4 * 8 * 8 + 2 * 8 * 16 = 512, and a head of 100 * 8: 1824
PROGRAM = {"d_model": 8, "d_ff": 16, "n_layers": 2, "vocab": 100}
PEAKS = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e5}
FLOPS = {"match": "^jit_decode$", "of": "flops"}
BYTES = {"match": "^jit_decode$", "of": "bytes", "bytes_per_param": 4}


def context(modules, work, chips=1):
    cell = type("Cell", (), {"name": "made", "chips": chips,
                             "config": {"program": PROGRAM}})()
    return {"cell": cell, "peaks": PEAKS, "trace": {"modules": modules},
            "segment": {"seconds": 1.0, "work": work}}


def test_both_shares_by_hand():
    # ten runs of jit_decode in 0.5 s on the device; a program of another
    # name beside it is not counted
    modules = {"jit_decode": {"seconds": 0.5, "count": 10},
               "jit_decode_more": {"seconds": 9.0, "count": 1},
               "jit_prefill": {"seconds": 0.3, "count": 2}}
    ctx = context(modules, {"decode_steps": 10, "active_slot_steps": 25})
    # 2 * 1824 * 25 = 91200 operations: 0.0912 s at the peak, of 0.5 s
    assert decode_step_share.read(ctx, FLOPS) == pytest.approx(18.24)
    # 4 * 1824 * 10 = 72960 bytes: 0.7296 s at the peak, of 0.5 s: over 100,
    # and not clipped
    assert decode_step_share.read(ctx, BYTES) == pytest.approx(145.92)
    # four chips hold four times the peak
    ctx = context(modules, {"decode_steps": 10, "active_slot_steps": 25}, chips=4)
    assert decode_step_share.read(ctx, FLOPS) == pytest.approx(4.56)


def test_nothing_where_no_decode_ran():
    work = {"decode_steps": 0, "active_slot_steps": 0}
    ctx = context({"jit_prefill": {"seconds": 0.3, "count": 2}}, work)
    assert decode_step_share.read(ctx, FLOPS) is None
    assert decode_step_share.read(ctx, BYTES) is None
    for missing in ("trace", "segment", "peaks"):
        assert decode_step_share.read(dict(ctx, **{missing: None}), FLOPS) is None


def test_nothing_on_the_recorded_trace_which_holds_no_decode():
    ctx = context(trace_reduce.reduce(RECORDED, chips=1)["modules"],
                  {"decode_steps": 0, "active_slot_steps": 0})
    assert ctx["trace"]["modules"]                      # programs ran, none of them decode
    assert decode_step_share.read(ctx, FLOPS) is None
    assert decode_step_share.read(ctx, BYTES) is None


def test_the_metric_files_name_the_reader_and_what_it_needs():
    for name, of in (("serve_decode_step_mfu", "flops"),
                     ("serve_decode_step_hbm_roofline", "bytes")):
        spec = harness.load_json(os.path.join(harness.HERE, "metrics", name + ".json"))
        assert spec["reader"] == "decode_step_share" and spec["args"]["of"] == of
        assert spec["args"]["match"] == "^jit_decode$"
    spec = harness.load_json(os.path.join(harness.HERE, "metrics",
                                          "serve_decode_kv_read_share.json"))
    assert spec["reader"] == "program_stat"
    assert spec["args"] == {"family": "generate", "key": "decode_kv_read_share",
                            "scale": 100.0}
