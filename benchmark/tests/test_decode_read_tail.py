"""The readers of a token's gap on the device's clock
(``benchmark/readers/decode_read_tail.py``, ``gc_ms_per_work.py``) on a
synthetic trace where the answer is known by hand."""
import gc

import pytest
from jax.profiler import ProfileData

from benchmark.readers import decode_read_tail, gc_ms_per_work, program_spans
from benchmark.tests.test_program_spans import plane

# The segment is 20-1000 us.  jit_decode runs five times inside it (ends 100,
# 200, 300, 420, 500) and once before it (0-2); a prefill runs between the
# third and the fourth.  The broker reads the ids of each: 50-105 (5 us after
# its run ended), 150-230 (30 us: the next run started at 210, has not ended),
# 240-302 (2 us), 350-440 (20 us after the fourth run, not the prefill); the
# read the segment's start cuts (1-30) and the one its end cuts (480-1100)
# are left out, and so is a dispatch span.
RUNS = [(0, 2), (10, 100), (110, 200), (210, 300), (310, 420), (430, 500)]
DEVICE = [("XLA Ops", [("%%f%d = f32[8] fusion(f32[8] %%p)" % i, a, b)
                       for i, (a, b) in enumerate(RUNS + [(302, 308)])]),
          ("XLA Modules", [("jit_decode(1)", a, b) for a, b in RUNS]
           + [("jit_prefill(2)", 302, 308)])]
READS = [(1, 30), (50, 105), (150, 230), (240, 302), (350, 440), (480, 1100)]
HOST = [("broker", [("bench_traced_segment", 20, 1000), ("mx.serve.decode.dispatch", 40, 45)]
         + [("mx.serve.decode.read", a, b) for a, b in READS]
         + [("mx.host.gc", 600, 623)]),
        ("client", [("mx.host.gc", 990, 1010)])]


def view(host=HOST, device=DEVICE):
    text = "\n".join([plane("/device:TPU:0", device)] if device else []) + "\n" + plane("/host:CPU", host)
    return program_spans.view(ProfileData.from_text_proto(text), chips=1)


@pytest.fixture()
def ctx(monkeypatch):
    def install(v, work=None):
        monkeypatch.setattr(program_spans, "for_context",
                            lambda c: v if c.get("trace") else None)
        cell = type("Cell", (), {"name": "synthetic", "chips": 1})()
        return {"cell": cell, "trace": {"modules": {}},
                "segment": {"seconds": 980e-6, "work": work or {"decode_steps": 5}}}
    return install


def test_each_read_is_paired_with_the_run_it_waited_for():
    v = view()
    assert decode_read_tail.pairs(v) == [(105e3, 100e3), (230e3, 200e3), (302e3, 300e3),
                                         (440e3, 420e3)]
    assert decode_read_tail.values(v, "lag") == [5e3, 30e3, 2e3, 20e3]
    assert decode_read_tail.values(v, "period") == [100e3, 100e3, 120e3]


def test_percentiles_in_milliseconds(ctx, monkeypatch):
    c = ctx(view())
    monkeypatch.setattr(decode_read_tail, "LEAST", 3)
    assert decode_read_tail.read(c, {"what": "lag", "q": 50}) == pytest.approx(0.0125)
    assert decode_read_tail.read(c, {"what": "lag", "q": 95}) == pytest.approx(0.0285)
    assert decode_read_tail.read(c, {"what": "period", "q": 95}) == pytest.approx(0.118)
    monkeypatch.setattr(decode_read_tail, "LEAST", 4)
    assert decode_read_tail.read(c, {"what": "lag", "q": 95}) is not None
    assert decode_read_tail.read(c, {"what": "period", "q": 95}) is None   # three periods


def test_a_late_host_does_not_make_a_period_of_nought():
    # the read of the run ending 420 returns after the next run has ended too
    host = [("broker", [("bench_traced_segment", 20, 1000), ("mx.serve.decode.read", 350, 505),
                        ("mx.serve.decode.read", 510, 520)])]
    v = view(host)
    assert decode_read_tail.pairs(v) == [(505e3, 500e3), (520e3, 500e3)]
    assert decode_read_tail.values(v, "period") == []


def test_nothing_without_read_spans_or_a_device_plane(ctx, monkeypatch):
    monkeypatch.setattr(decode_read_tail, "LEAST", 1)
    parent = [("broker", [h for h in HOST[0][1] if h[0] != "mx.serve.decode.read"])]
    for v in (view(parent), view(device=None)):
        c = ctx(v)
        for what in ("lag", "period"):
            assert decode_read_tail.read(c, {"what": what, "q": 95}) is None
    c = ctx(view())
    assert decode_read_tail.read({"cell": c["cell"], "trace": None, "segment": None},
                                 {"what": "lag", "q": 50}) is None


def test_collections_per_step(ctx, monkeypatch):
    import mxnet_tpu.profiler  # noqa: F401  registers the hook

    c = ctx(view())
    # 23 us on the broker, 10 of the client's 20 inside the segment
    assert gc_ms_per_work.read(c, {"work": "decode_steps"}) == pytest.approx(0.033 / 5)
    quiet = [("broker", [h for h in HOST[0][1] if h[0] != "mx.host.gc"])]
    assert gc_ms_per_work.read(ctx(view(quiet)), {"work": "decode_steps"}) == 0.0
    assert gc_ms_per_work.read(ctx(view(), {"decode_steps": 0}), {"work": "decode_steps"}) is None
    # a program that marks no collection: nothing
    monkeypatch.setattr(gc, "callbacks", [])
    assert gc_ms_per_work.read(ctx(view()), {"work": "decode_steps"}) is None
