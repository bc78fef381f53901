"""The reduction from a trace to what the metrics read, on a small trace
recorded on the chip (``benchmark/proof/record_tiny_trace.py``: three calls of
a jitted function that scans three matrix products and runs the flash
forward kernel once, each call followed by a wait and a 2 ms sleep)."""
import os

import pytest

from benchmark import trace_reduce
from benchmark.readers import (device_idle_share, kernel_time_share,
                               module_ms_per_run)

TRACE = os.path.join(os.path.dirname(__file__), "data", "tiny_trace.xplane.pb")
KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce(TRACE, chips=1)


def test_segment_is_the_benchmarks_own_span(summary):
    assert summary["segment_found"]
    assert 0.005 < summary["window_s"] < 0.05


def test_busy_is_the_union_of_the_device_operations(summary):
    # self times partition the union: nothing is counted twice under the while
    total = sum(r["seconds"] for r in summary["ops"].values())
    assert summary["busy_s"] == pytest.approx(total, rel=1e-6)
    assert 0 < summary["busy_s"] < summary["window_s"]


def test_kernels_and_programs_are_found_by_name(summary):
    kernels = [r for n, r in summary["ops"].items() if KERNEL in n]
    assert len(kernels) == 1 and kernels[0]["count"] == 3
    scanned = [r for n, r in summary["ops"].items() if "convolution" in n]
    assert sum(r["count"] for r in scanned) == 9          # 3 calls x 3 iterations
    assert summary["modules"]["jit_f"]["count"] == 3
    assert summary["modules"]["jit_f"]["seconds"] >= summary["busy_s"]


def test_gaps_are_labelled_by_the_host_span_that_covers_their_start(summary):
    gaps = dict(summary["top_gaps"])
    assert set(gaps) <= {"bench_train_step_dispatch", "bench_wait_device", "unlabelled"}
    assert sum(gaps.values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"], rel=0.02)
    assert len(summary["top_ops"]) <= 10 and len(summary["top_gaps"]) <= 10
    assert all(len(name) <= 80 for name, _s in summary["top_ops"])


def test_readers_read_the_summary_and_return_nothing_without_one(summary):
    ctx = {"trace": summary}
    idle = device_idle_share.read(ctx, {})
    assert 99.0 < idle < 100.0
    share = kernel_time_share.read(ctx, {"match": KERNEL})
    assert 40.0 < share < 70.0
    assert kernel_time_share.read(ctx, {"match": "no_such_kernel"}) is None
    assert module_ms_per_run.read(ctx, {"match": "^jit_f$"}) == pytest.approx(
        1000.0 * summary["modules"]["jit_f"]["seconds"] / 3)
    assert module_ms_per_run.read(ctx, {"match": "^jit_decode$"}) is None
    assert device_idle_share.read({"trace": None}, {}) is None


def test_a_trace_without_a_device_plane_reads_as_an_idle_chip(summary):
    """A traced segment in which no operation ran leaves no device plane (a
    serving window whose traced seconds hold no request): the recorded trace
    with its device planes taken out."""
    from types import SimpleNamespace

    data = trace_reduce._load(TRACE)
    host = SimpleNamespace(planes=[p for p in data.planes
                                   if not p.name.startswith("/device:")])
    idle = trace_reduce.reduce(TRACE, chips=1, data=host)
    assert idle["segment_found"] and idle["window_s"] == summary["window_s"]
    assert idle["busy_s"] == 0
    assert idle["ops"] == idle["modules"] == {} and idle["top_ops"] == []
    assert sum(s for _label, s in idle["top_gaps"]) == pytest.approx(idle["window_s"])
    assert device_idle_share.read({"trace": idle}, {}) == 100.0
    assert module_ms_per_run.read({"trace": idle}, {"match": "^jit_f$"}) is None


def test_self_times_take_children_off_their_parent():
    events = [(0, 100, "while", None), (10, 30, "a", None), (40, 90, "b", None),
              (50, 60, "c", None)]
    assert sorted(trace_reduce._self_times(events)) == [
        ("a", 20), ("b", 40), ("c", 10), ("while", 30)]


def test_short_name_keeps_result_opcode_and_target():
    text = ('%closed_call.64 = (bf16[64,2048,128]{2,1,0}) custom-call(bf16[64,2048,128] %x), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert trace_reduce.short_name(text) == "closed_call.64 custom-call tpu_custom_call"
    assert trace_reduce.short_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") \
        == "fusion.3 fusion"
