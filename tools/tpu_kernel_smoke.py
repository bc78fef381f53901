"""On-TPU Mosaic compile smoke for the fused Pallas kernels.

Compiles and executes every kernel entry point in
``mxnet_tpu.kernels.fused_block`` individually with ``interpret=False``
at real ResNet-50 shapes, checking each against the interpret-mode
result, so any Mosaic lowering failure surfaces with its error text
attached to the kernel that caused it.

Run:  python tools/tpu_kernel_smoke.py [--quick]
Writes a timestamped record to stdout; exit 0 iff everything compiled
and matched. This process holds the chip, so it starts no other: run
tools/bench_kernel.py and tune_kernels.py as their own commands.
"""
import argparse
import os
import datetime
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from mxnet_tpu.kernels import fused_block as fb  # noqa: E402


def _rand(key, shape, dtype=jnp.bfloat16):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _close(a, b, tol):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    denom = max(1.0, float(np.max(np.abs(b))))
    return float(np.max(np.abs(a - b))) / denom <= tol


_COMPILED = False  # interpret= value for the "compiled" side; main() may
# set it to None (auto) in --cpu plumbing-validation mode
_LOWER_ONLY = False  # --lower: cross-lower for TPU on the CPU host


def run_case(name, fn, tol=2e-2):
    """fn(interpret) -> pytree of arrays. Compare TPU vs interpret."""
    if _LOWER_ONLY:
        # Mosaic lowering (jaxpr -> TPU MLIR) happens at lowering time,
        # not execution time, so cross-lowering on the CPU host catches
        # every "NotImplementedError: ..." class of failure without the
        # chip. It cannot catch VMEM overflows or mosaic-to-LLO compile
        # errors — those still need the on-chip run.
        try:
            jax.jit(lambda: fn(False)).trace().lower(
                lowering_platforms=("tpu",))
            print(f"LOWER-OK {name}")
            return True
        except Exception:
            tb = traceback.format_exc()
            print(f"LOWER-FAIL {name}\n{tb[-1500:]}")
            return False
    try:
        got = jax.tree.map(np.asarray, fn(_COMPILED))
    except Exception:
        print(f"FAIL {name}\n{traceback.format_exc()}")
        return False
    want = jax.tree.map(np.asarray, fn(True))
    flat_g, _ = jax.tree.flatten(got)
    flat_w, _ = jax.tree.flatten(want)
    ok = all(_close(g, w, tol) for g, w in zip(flat_g, flat_w)
             if g is not None and w is not None)
    print(("PASS" if ok else "MISMATCH") + f" {name}")
    if not ok:
        for j, (g, w) in enumerate(zip(flat_g, flat_w)):
            if g is None:
                continue
            d = float(np.max(np.abs(np.asarray(g, np.float32)
                                    - np.asarray(w, np.float32))))
            print(f"  leaf {j}: shape {np.shape(g)} max_abs_diff {d:.4e}")
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shapes only")
    ap.add_argument("--cpu", action="store_true",
                    help="plumbing validation off-TPU: runs every case "
                         "interpret-vs-interpret so shape/arg bugs in the "
                         "harness itself surface without the chip")
    ap.add_argument("--lower", action="store_true",
                    help="Mosaic lowering check off-TPU: cross-lower every "
                         "case for the tpu platform on the CPU host; "
                         "catches lowering-rule failures without the chip")
    ap.add_argument("--mp", type=int, default=0, metavar="N",
                    help="after the smoke passes, run the megatron "
                         "tensor-parallel measurement on the (dp, mp=N) "
                         "mesh (tools/bench_e2e.measure_mp) in this "
                         "process: tokens/s, per-chip argument bytes vs "
                         "the replicated step (~1/N expected), exactly-2-"
                         "psums-per-block structural check (ISSUE 20)")
    args = ap.parse_args()

    if args.cpu or args.lower:
        jax.config.update("jax_platforms", "cpu")
        global _COMPILED, _LOWER_ONLY
        _COMPILED = None  # auto-interpret off-TPU
        _LOWER_ONLY = args.lower
    print("timestamp:", datetime.datetime.now(datetime.timezone.utc)
          .isoformat())
    print("backend:", jax.default_backend(), jax.devices())
    if jax.default_backend() != "tpu" and not (args.cpu or args.lower):
        print("NOT ON TPU — smoke is meaningless; aborting")
        return 2

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 16)
    results = []

    # shape sets: (N, H, W, Ci, Co) per conv flavor
    if args.quick:
        shapes = dict(n=2, h=16, w=16, c1=128, c2=32, c3=128)
    else:
        # stage-3 ResNet-50 bottleneck at batch 32: 16x16x1024, squeeze 256
        shapes = dict(n=8, h=16, w=16, c1=512, c2=128, c3=512)

    n, h, w = shapes["n"], shapes["h"], shapes["w"]
    c1, c2 = shapes["c1"], shapes["c2"]

    x = _rand(ks[0], (n, h, w, c1))
    scale = jax.random.uniform(ks[1], (c1,), jnp.float32, 0.5, 1.5)
    bias = jax.random.normal(ks[2], (c1,), jnp.float32) * 0.1

    # --- conv_fwd variants ---
    w11 = _rand(ks[3], (1, 1, c1, c2))
    results.append(run_case(
        "conv_fwd k1 s1 pro+stats",
        lambda it: fb.conv_fwd(x, w11, stride=1, prologue=(scale, bias, True),
                               emit_stats=True, interpret=it)))
    w33 = _rand(ks[4], (3, 3, c1, c2))
    results.append(run_case(
        "conv_fwd k3 s1 pro+stats",
        lambda it: fb.conv_fwd(x, w33, stride=1, prologue=(scale, bias, True),
                               emit_stats=True, interpret=it)))
    results.append(run_case(
        "conv_fwd k3 s2 pro",
        lambda it: fb.conv_fwd(x, w33, stride=2, prologue=(scale, bias, True),
                               interpret=it)))
    results.append(run_case(
        "conv_fwd k1 s2 nopro",
        lambda it: fb.conv_fwd(x, w11, stride=2, interpret=it)))

    # --- conv_wgrad variants ---
    g1 = _rand(ks[5], (n, h, w, c2))
    results.append(run_case(
        "conv_wgrad k1 s1 xpro",
        lambda it: fb.conv_wgrad(x, g1, (1, 1, c1, c2), stride=1,
                                 x_prologue=(scale, bias, True),
                                 interpret=it)))
    results.append(run_case(
        "conv_wgrad k3 s1 xpro",
        lambda it: fb.conv_wgrad(x, g1, (3, 3, c1, c2), stride=1,
                                 x_prologue=(scale, bias, True),
                                 interpret=it)))
    g2s = _rand(ks[6], (n, h // 2, w // 2, c2))
    results.append(run_case(
        "conv_wgrad k3 s2 xpro",
        lambda it: fb.conv_wgrad(x, g2s, (3, 3, c1, c2), stride=2,
                                 x_prologue=(scale, bias, True),
                                 interpret=it)))
    # g_bnbwd path: e, y_raw at output resolution, 5 consts over Co
    e = _rand(ks[7], (n, h, w, c2))
    y_raw = _rand(ks[8], (n, h, w, c2))
    cb = tuple(jax.random.normal(ks[9 + j], (c2,), jnp.float32) * 0.1
               for j in range(5))
    results.append(run_case(
        "conv_wgrad k3 s1 xpro+gbnbwd",
        lambda it: fb.conv_wgrad(x, (e, y_raw), (3, 3, c1, c2), stride=1,
                                 x_prologue=(scale, bias, True), g_bnbwd=cb,
                                 interpret=it)))

    # --- conv_dgrad variants ---
    w33T = _rand(ks[10], (3, 3, c1, c2))
    results.append(run_case(
        "conv_dgrad k3 s1 plain",
        lambda it: fb.conv_dgrad(g1, w33T, (n, h, w, c1), stride=1,
                                 interpret=it)))
    results.append(run_case(
        "conv_dgrad k3 s2 gbnbwd",
        lambda it: fb.conv_dgrad((_rand(ks[11], (n, h // 2, w // 2, c2)),
                                  _rand(ks[12], (n, h // 2, w // 2, c2))),
                                 w33T, (n, h, w, c1), stride=2, g_bnbwd=cb,
                                 interpret=it)))
    # out_mask epilogue (+stats): the conv3-bwd shape — dgrad through a
    # 1x1 (Ci=c1 -> Co=c2) conv, masked by the input's own BN/ReLU
    m_gamma = jax.random.uniform(ks[13], (c1,), jnp.float32, 0.5, 1.5)
    m_inv = jax.random.uniform(ks[14], (c1,), jnp.float32, 0.5, 1.5)
    results.append(run_case(
        "conv_dgrad k1 s1 outmask",
        lambda it: fb.conv_dgrad(g1, _rand(ks[15], (1, 1, c1, c2)),
                                 (n, h, w, c1), stride=1,
                                 out_mask=(x, m_gamma, bias,
                                           bias, m_inv),
                                 interpret=it)))

    # --- VMEM-pressure isolation: the single worst accumulator ---
    # 3x3x512x512 f32 wgrad accumulation = 9.4 MB resident across the
    # whole grid. Run it alone so a VMEM overflow is distinguishable
    # from a structural lowering failure in the smaller cases above.
    if not args.quick:
        xb = _rand(ks[0], (2, 8, 8, 512))
        gb = _rand(ks[1], (2, 8, 8, 512))
        results.append(run_case(
            "conv_wgrad k3 s1 VMEM-worst (512->512)",
            lambda it: fb.conv_wgrad(xb, gb, (3, 3, 512, 512), stride=1,
                                     interpret=it)))

    # --- full bottleneck unit fwd+bwd (train), both stride variants ---
    def unit_case(stride, csq, cin):
        data = _rand(ks[0], (n, h, w, cin))
        wu1 = _rand(ks[1], (1, 1, cin, csq))
        wu2 = _rand(ks[2], (3, 3, csq, csq))
        wu3 = _rand(ks[3], (1, 1, csq, cin))
        wsc = _rand(ks[4], (1, 1, cin, cin)) if stride == 2 else None
        gs = [jnp.ones((c,), jnp.float32) for c in (cin, csq, csq)]
        bs = [jnp.zeros((c,), jnp.float32) for c in (cin, csq, csq)]

        def fn(it):
            def loss(d, a1, a2, a3, asc):
                out, stats = fb.bottleneck_train(
                    d, a1, a2, a3, asc, gs[0], bs[0], gs[1], bs[1],
                    gs[2], bs[2], stride, 1e-5, it)
                return jnp.sum(out.astype(jnp.float32) ** 2) * 1e-4
            val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(
                data, wu1, wu2, wu3, wsc)
            return (val,) + grads
        return fn

    results.append(run_case("bottleneck_train s1 fwd+bwd",
                            unit_case(1, c2, c1), tol=5e-2))
    results.append(run_case("bottleneck_train s2 fwd+bwd",
                            unit_case(2, c2, c1), tol=5e-2))

    ok = all(results)
    print(f"{'ALL PASS' if ok else 'FAILURES'}: "
          f"{sum(results)}/{len(results)}")
    if args.mp and args.mp > 1 and ok and not _LOWER_ONLY:
        # parity first, sharding second: the mp measurement reuses the
        # smoke-validated backend. Prints one JSON line (tokens/s,
        # per-chip bytes ratio, collective counts) — the on-chip half
        # of the ISSUE 20 acceptance; works on the --cpu host mesh too.
        import json

        from tools.bench_e2e import measure_mp
        print("--- tensor-parallel (mp=%d) step ---" % args.mp,
              flush=True)
        print(json.dumps(measure_mp(mp=args.mp)))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
