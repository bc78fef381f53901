"""Loop-amortized per-kernel timing: fused Pallas kernels vs the
identical XLA graph.

The round-5 harness timed one dispatch at a time and contradicted
itself (2.7x in one run, parity in a repeat): at sub-0.1 ms per call
the dispatch latency swamps the kernel. This rewrite runs each kernel N
iterations inside ONE jitted ``lax.scan`` and times the whole program, so dispatch cost amortizes to
nothing and per-iteration time is the kernel itself. A tiny
(*1e-30-scaled*) data dependence feeds each iteration's output back
into the next iteration's input, so XLA cannot hoist or CSE the kernel
out of the loop; the values are bit-identical in bf16.

Each timing repeats ``--repeats`` times (default 9) and reports the
trimmed mean and run-to-run spread ((max-min)/mean over the middle
runs, ``repeats//3`` dropped from EACH end — this container's shared
CPU shows ~65% max-min spread on *fixed* numpy work, so the extremes
measure steal time, not the kernel; raw runs ride the JSON record, so
the full distribution stays auditable). The bar is <10% spread, where
the round-5 single-dispatch harness showed 170%.

Run on a TPU host:

    python tools/bench_kernel.py                # stage-3 shapes, N=1000
    python tools/bench_kernel.py --row-tile 8   # sweep the row-tile knob

On CPU hosts the Pallas kernels run in interpret mode at a reduced
default shape/iteration count — that validates the harness (and its
variance bound), not the kernels' speed. ``bench.py`` starts this tool
as its ``kernels`` variant; the last stdout line is the JSON summary it
ingests.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax            # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax   # noqa: E402

# the loop-amortized timing harness now lives in mxnet_tpu/tune/harness.py
# (ISSUE 10: the schedule search times candidates with the SAME scan
# discipline) — imported lazily so `--cpu` platform selection still
# happens before any backend touch
def _harness():
    from mxnet_tpu.tune import harness

    return harness


def _make_run(fn, iters):
    return _harness().make_run(fn, iters)


def _clock():
    return _harness().clock()


def prepare_run(fn, operands, iters, target_sec=0.5, min_iters=10):
    return _harness().prepare_run(fn, operands, iters,
                                  target_sec=target_sec,
                                  min_iters=min_iters)


def summarize(runs):
    return _harness().summarize(runs)


def _case_args(batch, hw, ci, co, k):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (batch, hw, hw, ci),
                          jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(ks[1], (k, k, ci, co),
                          jnp.float32).astype(jnp.bfloat16)
    scale = jax.random.uniform(ks[2], (ci,), jnp.float32, 0.5, 1.5)
    bias = jax.random.normal(ks[3], (ci,), jnp.float32) * 0.1
    return x, w, scale, bias


def _xla_conv_fwd(x, w, scale, bias):
    """The exact unfused graph of conv_fwd(prologue, emit_stats)."""
    hv = jnp.maximum(x.astype(jnp.float32) * scale + bias,
                     0.0).astype(x.dtype)
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    pad = "SAME" if w.shape[0] == 3 else "VALID"
    y = lax.conv_general_dilated(
        hv, w, (1, 1), pad, dimension_numbers=dn,
        preferred_element_type=jnp.float32).astype(x.dtype)
    yf = y.astype(jnp.float32)
    s = jnp.stack([jnp.sum(yf, axis=(0, 1, 2)),
                   jnp.sum(yf * yf, axis=(0, 1, 2))])
    return y, s


def _unit_args(batch, hw, cin, csq):
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 8)
    f = lambda k_, s: jax.random.normal(k_, s, jnp.float32)  # noqa: E731
    data = f(ks[0], (batch, hw, hw, cin)).astype(jnp.bfloat16)
    w1 = f(ks[1], (1, 1, cin, csq)).astype(jnp.bfloat16)
    w2 = f(ks[2], (3, 3, csq, csq)).astype(jnp.bfloat16)
    w3 = f(ks[3], (1, 1, csq, cin)).astype(jnp.bfloat16)
    gs = [jnp.ones((c,), jnp.float32) for c in (cin, csq, csq)]
    bs = [jnp.zeros((c,), jnp.float32) for c in (cin, csq, csq)]
    return data, w1, w2, w3, gs, bs


def _xla_unit(data, w1, w2, w3, gs, bs, eps=1e-5):
    def bn_relu(x, g, b):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, (0, 1, 2))
        var = jnp.maximum(jnp.mean(xf * xf, (0, 1, 2)) - mean * mean, 0.0)
        inv = lax.rsqrt(var + eps)
        return jnp.maximum((xf - mean) * inv * g + b, 0.0).astype(x.dtype)

    def conv(x, w):
        # no preferred_element_type: its transpose rule feeds an f32
        # cotangent to a bf16 conv under grad; XLA:TPU accumulates bf16
        # convs in f32 internally regardless
        pad = "SAME" if w.shape[0] == 3 else "VALID"
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NHWC", "HWIO", "NHWC"))
        return lax.conv_general_dilated(x, w, (1, 1), pad,
                                        dimension_numbers=dn)

    y = conv(bn_relu(data, gs[0], bs[0]), w1)
    y = conv(bn_relu(y, gs[1], bs[1]), w2)
    y = conv(bn_relu(y, gs[2], bs[2]), w3)
    return y + data


def _conv_plan_meta(fb, x_shape, w_shape, tuned=False):
    """The mxu_plan summary + schedule-table key riding a pallas conv
    timing record, so bench records and schedule-table entries are
    join-able by key (ISSUE 10 satellite). Under ``--tuned`` the plan
    is computed with the schedule the kernel will actually consult —
    the record must describe the program that was timed."""
    from mxnet_tpu.tune import get_table, make_key
    from mxnet_tpu.tune.search import plan_summary

    n, hw, _hw2, ci = x_shape
    k = int(w_shape[0])
    co = int(w_shape[-1])
    key_shape = (n, hw, hw, ci, co, k, 1)
    sched = None
    if tuned:
        sched = get_table().lookup("fused_fwd", key_shape, "bfloat16",
                                   jax.default_backend(),
                                   record_stats=False)
        if sched and not fb.schedule_legal("fwd", x_shape, w_shape, 1,
                                           sched)[0]:
            sched = None  # the kernel falls back too (_schedule_knobs)
    meta = {
        "mxu_plan": plan_summary(fb.mxu_plan("fwd", x_shape, w_shape,
                                             stride=1, schedule=sched)),
        "schedule_key": make_key("fused_fwd", key_shape, "bfloat16",
                                 jax.default_backend()),
    }
    if sched:
        meta["tuned_schedule"] = sched
    return meta


def build_cases(args, fb):
    """(name, fn, operands, flops_per_iter, meta) — fn's first operand
    is the scan carry; meta (plan summary + schedule key) rides the
    pallas conv records, None elsewhere."""
    n, hw, ci, co = args.batch, args.hw, args.ci, args.co
    cases = []

    x, w33, scale, bias = _case_args(n, hw, ci, co, 3)
    fl3 = 2 * n * hw * hw * ci * co * 9
    cases.append(("conv3x3_fwd_pallas",
                  lambda x_, w_, s_, b_: fb.conv_fwd(
                      x_, w_, stride=1, prologue=(s_, b_, True),
                      emit_stats=True),
                  (x, w33, scale, bias), fl3,
                  _conv_plan_meta(fb, x.shape, w33.shape, args.tuned)))
    cases.append(("conv3x3_fwd_xla", _xla_conv_fwd,
                  (x, w33, scale, bias), fl3, None))

    x1, w11, scale1, bias1 = _case_args(n, hw, ci, co, 1)
    fl1 = 2 * n * hw * hw * ci * co
    cases.append(("conv1x1_fwd_pallas",
                  lambda x_, w_, s_, b_: fb.conv_fwd(
                      x_, w_, stride=1, prologue=(s_, b_, True),
                      emit_stats=True),
                  (x1, w11, scale1, bias1), fl1,
                  _conv_plan_meta(fb, x1.shape, w11.shape, args.tuned)))
    cases.append(("conv1x1_fwd_xla", _xla_conv_fwd,
                  (x1, w11, scale1, bias1), fl1, None))

    data, w1, w2, w3, gs, bs = _unit_args(n, hw, args.unit_cin, ci)
    flu = (2 * n * hw * hw * args.unit_cin * ci * 2
           + 2 * n * hw * hw * ci * ci * 9)
    eps = 1e-5

    def pallas_unit_fwdbwd(d_, a1, a2, a3):
        def loss(d, b1_, b2_, b3_):
            out, _ = fb.bottleneck_train(d, b1_, b2_, b3_, None,
                                         gs[0], bs[0], gs[1], bs[1],
                                         gs[2], bs[2], 1, eps, None)
            return jnp.sum(out.astype(jnp.float32) ** 2) * 1e-6
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(d_, a1, a2, a3)

    def xla_unit_fwdbwd(d_, a1, a2, a3):
        def loss(d, b1_, b2_, b3_):
            out = _xla_unit(d, b1_, b2_, b3_, gs, bs, eps)
            return jnp.sum(out.astype(jnp.float32) ** 2) * 1e-6
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(d_, a1, a2, a3)

    cases.append(("unit_fwdbwd_pallas", pallas_unit_fwdbwd,
                  (data, w1, w2, w3), 3 * flu, None))
    cases.append(("unit_fwdbwd_xla", xla_unit_fwdbwd,
                  (data, w1, w2, w3), 3 * flu, None))
    return cases


def main(argv=None):
    on_tpu = None
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--hw", type=int, default=None,
                    help="spatial size (stage-3 default: 14)")
    ap.add_argument("--ci", type=int, default=None)
    ap.add_argument("--co", type=int, default=None)
    ap.add_argument("--unit-cin", type=int, default=None,
                    help="bottleneck unit input channels (4*ci default)")
    ap.add_argument("--iters", type=int, default=None,
                    help="scan length per timed program (default: "
                         "calibrated to ~--target-sec per run, >=1000 "
                         "iterations on TPU)")
    ap.add_argument("--target-sec", type=float, default=None,
                    help="calibrated duration of one timed program "
                         "(default 0.5 on TPU, 1.0 on CPU)")
    ap.add_argument("--repeats", type=int, default=9)
    ap.add_argument("--row-tile", type=int, default=None,
                    help="set the fused-kernel row-tile knob for this run")
    ap.add_argument("--tuned", action="store_true",
                    help="let the kernels consult the on-disk schedule "
                         "table (tools/tune_kernels.py winners); default "
                         "pins the hand schedules so bench records stay "
                         "comparable across rounds")
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU/interpret (harness validation mode)")
    args = ap.parse_args(argv)

    # default-untuned: a populated schedule table on the host must not
    # silently shift the trajectory numbers (the `tune` bench variant
    # reports winner-vs-default explicitly)
    os.environ["MXNET_TPU_TUNE"] = "1" if args.tuned else "0"
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from mxnet_tpu.context import device_record, kernel_platform

    on_tpu = kernel_platform() == "tpu"
    if not on_tpu:
        _harness().pin_single_core()
    # CPU runs validate the harness (variance bound), not kernel speed:
    # interpret-mode Pallas is orders of magnitude off, so default to a
    # small shape and short scan that still gives >=100 ms per timed run
    if args.batch is None:
        args.batch = 64 if on_tpu else 2
    if args.hw is None:
        args.hw = 14 if on_tpu else 8
    if args.ci is None:
        args.ci = 256 if on_tpu else 32
    if args.co is None:
        args.co = args.ci
    if args.unit_cin is None:
        args.unit_cin = 4 * args.ci if on_tpu else 2 * args.ci
    min_iters = 1000 if on_tpu else 10
    if args.target_sec is None:
        args.target_sec = 0.5 if on_tpu else 1.0

    from mxnet_tpu.kernels import fused_block as fb
    if args.row_tile is not None:
        fb.set_row_tile(args.row_tile)

    print("backend: %s  shape: batch=%d hw=%d ci=%d co=%d  iters=%s "
          "repeats=%d row_tile=%s"
          % (jax.default_backend(), args.batch, args.hw, args.ci, args.co,
             args.iters or "auto", args.repeats, args.row_tile))
    # two-phase, round-robin: compile + warm every kernel FIRST, then
    # interleave the timed runs across kernels — each repeat of every
    # kernel samples the same machine-noise epoch, so sustained drift
    # (this host moves 2-3x over minutes) hits all variants alike and
    # the pallas/xla comparison cannot flip on scheduling luck
    # interpret left to the kernels: Mosaic on tpu, interpret mode on cpu
    cases = build_cases(args, fb)
    prepared = []
    for name, fn, operands, flops, meta in cases:
        run, x0, rest, iters = prepare_run(
            fn, operands, args.iters, target_sec=args.target_sec,
            min_iters=min_iters)
        prepared.append((name, run, x0, rest, iters, flops, meta))
    clock = _clock()

    # CPU drift normalization: this shared host's effective speed
    # drifts continuously (fixed numpy work moves 50-80% between runs
    # — memory contention from co-tenants), so raw per-run times can
    # never replicate to 10%. A fixed jitted matmul scan is timed
    # immediately before every kernel run; scaling each run by
    # (median calibration / its calibration) cancels the drift both
    # measurements share. TPU timing is device-side and needs none.
    calib = None
    if not on_tpu:
        ck = jnp.ones((256, 256), jnp.float32)
        calib = prepare_run(lambda a: (a @ a) / 256.0, (ck,), None,
                            target_sec=min(0.25, args.target_sec / 2),
                            min_iters=5)
    all_runs = {name: [] for name, *_ in prepared}
    all_calib = {name: [] for name, *_ in prepared}
    for _ in range(args.repeats):
        for name, run, x0, rest, iters, _fl, _meta in prepared:
            if calib is not None:
                crun, cx, crest, citers = calib
                t0 = clock()
                crun(cx, crest).block_until_ready()
                all_calib[name].append(clock() - t0)
            t0 = clock()
            run(x0, rest).block_until_ready()
            all_runs[name].append((clock() - t0) / iters * 1e3)
    cflat = sorted(c for cs in all_calib.values() for c in cs)
    cmed = cflat[len(cflat) // 2] if cflat else None

    summary = {}
    for name, _run, _x0, _rest, iters, flops, meta in prepared:
        raw = all_runs[name]
        if cmed:
            runs = [r * cmed / c if c else r
                    for r, c in zip(raw, all_calib[name])]
        else:
            runs = raw
        mean, spread = summarize(runs)
        tflops = flops / (mean * 1e-3) / 1e12 if mean else 0.0
        rec = {"ms_per_iter": round(mean, 4),
               "spread_pct": round(spread * 100, 2),
               "tflops": round(tflops, 2),
               "iters": iters, "repeats": args.repeats,
               "runs_ms": [round(r, 4) for r in runs]}
        if cmed:
            rec["drift_normalized"] = True
            rec["raw_runs_ms"] = [round(r, 4) for r in raw]
        if meta:
            rec.update(meta)
        summary[name] = rec
        print("%-22s %8.4f ms/iter  %7.2f TFLOP/s  spread %5.2f%%"
              % (name, mean, tflops, spread * 100))

    # the decision-relevant number is the pallas/xla RATIO: each
    # repeat's pair of runs is adjacent in the round-robin, so the
    # per-repeat ratio cancels whatever the host was doing that second
    # and replicates far tighter than either absolute time
    ratios = {}
    for a in ("conv3x3_fwd", "conv1x1_fwd", "unit_fwdbwd"):
        p, x_ = all_runs.get(a + "_pallas"), all_runs.get(a + "_xla")
        if not (p and x_):
            continue
        per = [pr / xr for pr, xr in zip(p, x_) if xr]
        if not per:    # micro-runs can round to 0.0 process-CPU ms
            continue
        rmean, rspread = summarize(per)
        ratios[a] = {"pallas_over_xla": round(rmean, 3),
                     "spread_pct": round(rspread * 100, 2)}
        print("%-22s pallas/xla = %.2fx  (per-repeat spread %5.2f%%)"
              % (a, rmean, rspread * 100))
    worst = max((r["spread_pct"] for r in ratios.values()),
                default=max((r["spread_pct"] for r in summary.values()),
                            default=0.0))
    print(json.dumps({"bench_kernel": summary, "ratios": ratios,
                      "device": device_record(),
                      "row_tile": args.row_tile,
                      "tuned": bool(args.tuned),
                      "worst_spread_pct": worst}))
    return 0 if worst < 10.0 else 4


if __name__ == "__main__":
    sys.exit(main())
