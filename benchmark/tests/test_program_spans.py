"""The readers of the program's own spans, on a synthetic trace where the
answer is known by hand, and on a small trace recorded on the chip
(``benchmark/proof/record_span_trace.py``)."""
import os
import shutil

import pytest
from jax.profiler import ProfileData

from benchmark import harness, trace_reduce
from benchmark.readers import (idle_under_span, program_spans, program_stat,
                               scope_device_ms_per_work, span_ms_per_work)

RECORDED = os.path.join(os.path.dirname(__file__), "data", "span_trace.xplane.pb")


def plane(name, lines):
    """One XPlane in text form; times in microseconds."""
    names = sorted({n for _l, events in lines for n, _a, _b in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = ['planes { name: "%s"' % name]
    for i, (line_name, events) in enumerate(lines):
        out.append('  lines { id: %d name: "%s" timestamp_ns: 0' % (i + 1, line_name))
        for n, a, b in events:
            out.append("    events { metadata_id: %d offset_ps: %d duration_ps: %d }"
                       % (ids[n], a * 10 ** 6, (b - a) * 10 ** 6))
        out.append("  }")
    for n, i in ids.items():
        out.append('  event_metadata { key: %d value { id: %d name: "%s" } }' % (i, i, n))
    return "\n".join(out + ["}"])


# A 1000 us segment.  The device runs four operations and is idle in three
# gaps: 100-300, 400-700, 800-950 (650 us).  The trainer's thread holds one
# batch 50-900 with a copy 120-290 and a callback 380-480 whose drain is
# 390-420, and the head of a second batch the segment cuts (980-1100); a
# client thread submits 600-650.  A gap's start lies under another span than
# most of the gap does: labelled by their start, as ``trace_reduce.top_gaps``
# does, the three gaps go 200 us to the batch, 300 us to the drain and 150 us
# to the batch.
DEVICE = [("XLA Ops", [("%a = f32[8] fusion(f32[8] %p)", 0, 100), ("%b = f32[8] copy(f32[8] %a)", 300, 400),
                       ("%c = f32[8] fusion(f32[8] %b)", 700, 800),
                       ("%d = f32[8] fusion(f32[8] %c)", 950, 1000)]),
          ("XLA Modules", [("jit_step(1)", 300, 400), ("jit_step(1)", 700, 800)])]
HOST = [("trainer", [("bench_traced_segment", 0, 1000), ("bench_metric_read", 380, 480),
                     ("mx.fit.batch", 50, 900), ("mx.fit.h2d", 120, 290),
                     ("mx.fit.callbacks", 380, 480), ("mx.metric.drain", 390, 420),
                     ("mx.fit.batch", 980, 1100)]),
        ("client", [("mx.serve.submit", 600, 650)])]


@pytest.fixture(scope="module")
def synthetic():
    text = plane("/device:TPU:0", DEVICE) + "\n" + plane("/host:CPU", HOST)
    return program_spans.view(ProfileData.from_text_proto(text), chips=1)


def us(seconds):
    return round(seconds * 1e6, 6)


def test_idle_is_split_by_overlap_not_by_where_a_gap_starts(synthetic):
    shares = {k: us(v) for k, v in program_spans.idle_by_span(synthetic).items()}
    assert shares == {"mx.fit.h2d": 170, "mx.metric.drain": 20, "mx.fit.callbacks": 60,
                      "mx.serve.submit": 50, "mx.fit.batch": 300, None: 50}
    assert sum(shares.values()) == 650
    # what labelling by the start of each gap would have said
    assert shares["mx.metric.drain"] != 300 and shares["mx.fit.batch"] != 350


def test_a_span_is_counted_as_far_as_the_segment_holds_it(synthetic):
    seconds, count = program_spans.span_seconds(synthetic, "mx.fit.batch")
    assert us(seconds) == 850 + 20 and count == pytest.approx(1 + 20 / 120)
    # self time: less the copy and the callbacks on its own thread, not the
    # client's submit, and not the drain twice
    seconds, _ = program_spans.span_seconds(synthetic, "mx.fit.batch", True)
    assert us(seconds) == 870 - 170 - 100
    seconds, _ = program_spans.span_seconds(synthetic, "mx.fit.callbacks", True)
    assert us(seconds) == 100 - 30
    assert program_spans.span_seconds(synthetic, "mx.fit.update") == (0.0, 0.0)


def context(view, work):
    """A reader's context whose trace is ``view``."""
    cell = type("Cell", (), {"name": "synthetic", "chips": 1})()
    program_spans._CACHE.clear()
    return {"cell": cell, "segment": {"seconds": 1e-3, "work": work},
            "trace": {"modules": {}}}, view


@pytest.fixture()
def readers_see(monkeypatch):
    def install(view):
        monkeypatch.setattr(program_spans, "for_context",
                            lambda ctx: view if ctx.get("trace") else None)
    return install


def test_readers_on_the_synthetic_trace(synthetic, readers_see):
    readers_see(synthetic)
    ctx, _ = context(synthetic, {"batches": 2})
    assert idle_under_span.read(ctx, {"span": "mx.fit.h2d"}) == pytest.approx(100 * 170 / 650)
    assert idle_under_span.read(ctx, {"span": None}) == pytest.approx(100 * 50 / 650)
    assert idle_under_span.read(ctx, {"span": "mx.fit.update"}) == 0.0
    every = [None] + sorted({s[2] for s in synthetic["spans"]})
    assert sum(idle_under_span.read(ctx, {"span": n}) for n in every) == pytest.approx(100.0)
    assert span_ms_per_work.read(ctx, {"span": "mx.fit.h2d", "work": "batches"}) \
        == pytest.approx(0.170 / 2)
    assert span_ms_per_work.read(ctx, {"span": "mx.fit.batch", "work": "batches",
                                       "self": True}) == pytest.approx(0.600 / 2)
    # per span, less the time the program it waited for ran under it: both
    # runs of jit_step under the batch, the first's last 10 us under the drain
    assert span_ms_per_work.read(ctx, {"span": "mx.fit.batch", "work": "spans",
                                       "less_module": "^jit_step$"}) \
        == pytest.approx((0.870 - 0.200) / (1 + 20 / 120))
    assert span_ms_per_work.read(ctx, {"span": "mx.metric.drain", "work": "spans",
                                       "less_module": "^jit_step$"}) \
        == pytest.approx(0.030 - 0.010)
    assert span_ms_per_work.read(ctx, {"span": "mx.metric.drain", "work": "spans",
                                       "less_module": "^jit_other$"}) \
        == pytest.approx(0.030)
    assert span_ms_per_work.read(ctx, {"span": "mx.fit.update", "work": "batches"}) is None
    assert span_ms_per_work.read(ctx, {"span": "mx.fit.h2d", "work": "steps"}) is None


def test_readers_return_nothing_for_a_program_without_spans(readers_see):
    bare = program_spans.view(ProfileData.from_text_proto(
        plane("/device:TPU:0", DEVICE) + "\n"
        + plane("/host:CPU", [("trainer", [("bench_traced_segment", 0, 1000)])])))
    assert bare["spans"] == [] and us(sum(b - a for a, b in bare["idle"][0]) / 1e9) == 650
    readers_see(bare)
    ctx, _ = context(bare, {"batches": 2})
    assert idle_under_span.read(ctx, {"span": None}) is None
    assert span_ms_per_work.read(ctx, {"span": "mx.fit.h2d", "work": "batches"}) is None
    # and for a run that was not traced
    for reader, args in ((idle_under_span, {"span": None}),
                         (span_ms_per_work, {"span": "mx.fit.h2d", "work": "batches"}),
                         (scope_device_ms_per_work, {"scope": "mx.opt.update", "work": "steps"})):
        assert reader.read({"cell": ctx["cell"], "trace": None, "segment": None}, args) is None


def test_program_stat_asks_the_program():
    from mxnet_tpu import profiler

    profiler.generate_reset()
    assert program_stat.read({}, {"family": "generate", "key": "queue_wait_p50_ms"}) is None
    profiler.generate_record(queue_waits=[0.1, 0.2, 0.3], decode_steps=4,
                             decode_steps_after_prefill=1, loop_seconds=1.0,
                             prefill_seconds=0.2, decode_seconds=0.6)
    try:
        assert program_stat.read({}, {"family": "generate",
                                      "key": "queue_wait_p50_ms"}) == pytest.approx(200.0)
        assert program_stat.read({}, {"family": "generate", "scale": 100.0,
                                      "key": "decode_after_prefill_share"}) == 25.0
        assert program_stat.read({}, {"family": "generate",
                                      "key": "loop_host_ms_per_step"}) == pytest.approx(50.0)
        assert program_stat.read({}, {"family": "generate", "key": "no_such"}) is None
        assert program_stat.read({}, {"family": "no_such", "key": "x"}) is None
    finally:
        profiler.generate_reset()


# -- the recorded trace ---------------------------------------------------------
@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The readers' context for the recorded trace, laid out where a traced
    run of a cell called ``recorded`` would have left it (xprof writes a
    cache beside the file it converts, so it reads a copy)."""
    out = tmp_path_factory.mktemp("bench_out")
    os.makedirs(os.path.join(out, "trace", "recorded"))
    shutil.copy(RECORDED, os.path.join(out, "trace", "recorded", "t.xplane.pb"))
    old, harness.OUT_DIR = harness.OUT_DIR, str(out)
    program_spans._CACHE.clear()
    cell = type("Cell", (), {"name": "recorded", "chips": 1})()
    summary = trace_reduce.reduce(RECORDED, chips=1)
    yield {"cell": cell, "trace": summary,
           "segment": {"seconds": summary["window_s"], "work": {"batches": 4, "steps": 2}}}
    harness.OUT_DIR = old
    program_spans._CACHE.clear()


def test_recorded_idle_shares_sum_to_all_of_idle(recorded):
    v = program_spans.for_context(recorded)
    names = sorted({s[2] for s in v["spans"]})
    assert {"mx.fit.batch", "mx.fit.forward_backward", "mx.fit.dispatch", "mx.fit.h2d",
            "mx.fit.callbacks", "mx.metric.drain", "mx.fit.epoch_end"} <= set(names)
    shares = {n: idle_under_span.read(recorded, {"span": n}) for n in [None] + names}
    assert sum(shares.values()) == pytest.approx(100.0, abs=1e-6)
    idle = recorded["trace"]["window_s"] - recorded["trace"]["busy_s"]
    assert sum(program_spans.idle_by_span(v).values()) == pytest.approx(idle, rel=1e-6)
    # four batches, each copying its two arrays and read once by the callback
    assert program_spans.span_seconds(v, "mx.fit.batch")[1] == 4
    assert program_spans.span_seconds(v, "mx.fit.h2d")[1] == 8
    assert span_ms_per_work.read(recorded, {"span": "mx.metric.drain", "work": "batches"}) > 0
    batch = span_ms_per_work.read(recorded, {"span": "mx.fit.batch", "work": "batches"})
    own = span_ms_per_work.read(recorded, {"span": "mx.fit.batch", "work": "batches",
                                           "self": True})
    assert 0 < own < batch


def test_recorded_kernels_and_scopes_are_found_by_the_names_the_program_chose(recorded):
    total = 0.0
    for kernel in ("mx_flash_fwd", "mx_flash_dq", "mx_flash_dkv"):
        ms = scope_device_ms_per_work.read(recorded, {"op": "^%s\\b" % kernel,
                                                      "work": "steps"})
        assert ms > 0, kernel
        total += ms
    # the three names cover exactly what the outside-in match on the custom
    # call's target finds
    seconds, calls = trace_reduce.matching(recorded["trace"]["ops"],
                                           'custom_call_target="tpu_custom_call"')
    assert calls == 3 * 3 * 2                         # kernels x layers x steps
    assert total * 2 / 1e3 == pytest.approx(seconds, rel=1e-3)
    assert scope_device_ms_per_work.read(recorded, {"scope": "mx.lm.attn",
                                                    "work": "steps"}) >= total
    # and the instruction itself carries the kernel's name
    assert any(n.startswith("%mx_flash_dkv") for n in recorded["trace"]["ops"])
    assert scope_device_ms_per_work.read(recorded, {"scope": "mx.opt.update",
                                                    "work": "steps"}) > 0
    assert scope_device_ms_per_work.read(recorded, {"scope": "mx.no.such",
                                                    "work": "steps"}) is None
