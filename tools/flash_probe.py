"""Time the three flash-attention kernels of one layer on the chip.

One process, one shape a case: the jitted gradient of ``flash_attention``
(forward, dQ and dK/dV kernels, with whatever XLA puts around them) is run
under ``jax.profiler.trace`` and the device time of each custom call is read
from the trace by its name (``mx_flash_fwd`` / ``mx_flash_dq`` /
``mx_flash_dkv``), the rest as ``other_ms``. Every case prints one JSON line
and appends it to ``chiprun_out/flash_probe/<label>.jsonl``.

    chiprun -- python3 tools/flash_probe.py --sweep            # block sweep
    chiprun -- python3 tools/flash_probe.py --causes --check \
        --parent-file .chip_parent/mxnet_tpu/kernels/flash_attention.py
    chiprun -- python3 tools/flash_probe.py --prefill          # serving buckets

``--causes`` takes the kernel as it is and gives back one cause at a time
(float32 operands, head dim padded to 128, 128 x 128 blocks); with
``--parent-file`` the file of another commit, or a variant of this one, is
timed beside it under the same harness. Without a TPU it exits 2: a CPU timing is no
device metric (``--allow-cpu`` runs tiny shapes in interpret mode to check
the plumbing and prints no time as a device time).
"""
import argparse
import glob
import importlib.util
import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import mxnet_tpu.kernels.flash_attention  # noqa: E402,F401  (a module, not the function)

fa = sys.modules["mxnet_tpu.kernels.flash_attention"]

KERNELS = ("mx_flash_fwd", "mx_flash_dq", "mx_flash_dkv")


def _load_parent(path):
    """Another commit's ``flash_attention.py`` as a sibling module of this
    tree's, so that its relative imports find this tree's package."""
    spec = importlib.util.spec_from_file_location(
        "mxnet_tpu.kernels._other_%d" % len(sys.modules), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _device_times(trace_dir):
    """{kernel: [seconds, calls]} and the other operations' seconds, from
    the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    data = ProfileData.from_file(path)
    per = {k: [0.0, 0] for k in KERNELS}
    other = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                for k in KERNELS:
                    if k in e.name:
                        per[k][0] += e.duration_ns * 1e-9
                        per[k][1] += 1
                        break
                else:
                    other[e.name] = other.get(e.name, 0.0) + e.duration_ns * 1e-9
    return per, other


def _emit(rec, out):
    print(json.dumps(rec), flush=True)
    with open(out, "a") as f:
        f.write(json.dumps(rec) + "\n")


def run_case(label, module, shape, dtype, *, causal=True, block_q=None,
             block_k=None, pad_d=False, grad=True, iters=5, on_tpu=True,
             out):
    b, h, s, d = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                   for kk in keys)
    scale = 1.0 / d ** 0.5

    def attend(q, k, v):
        if pad_d:
            q, k, v = (jnp.pad(x, ((0, 0),) * 3 + ((0, 128 - d),))
                       for x in (q, k, v))
        o = module.flash_attention(q, k, v, causal=causal, sm_scale=scale,
                                   block_q=block_q, block_k=block_k)
        return o[..., :d]

    if grad:
        def fn(q, k, v, do):
            o, vjp = jax.vjp(attend, q, k, v)
            return (o,) + vjp(do)
    else:
        def fn(q, k, v, do):
            return attend(q, k, v)
    rec = {"label": label, "shape": list(shape), "dtype": jnp.dtype(dtype).name,
           "causal": causal, "block_q": block_q, "block_k": block_k,
           "pad_d": pad_d, "grad": grad, "iters": iters,
           "platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind}
    try:
        step = jax.jit(fn)
        jax.block_until_ready(step(q, k, v, do))
        jax.block_until_ready(step(q, k, v, do))
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(iters):
                    r = step(q, k, v, do)
                jax.block_until_ready(r)
            if on_tpu:
                per, other = _device_times(tmp)
                for kern in KERNELS:
                    secs, calls = per[kern]
                    rec[kern + "_ms"] = 1e3 * secs / calls if calls else None
                rec["other_ms"] = 1e3 * sum(other.values()) / iters
                rec["other_top"] = [
                    [n, round(1e3 * t / iters, 4)] for n, t in
                    sorted(other.items(), key=lambda kv: -kv[1])[:4]]
    except Exception as e:  # noqa: BLE001 - a refused block is a result
        rec["error"] = str(e).splitlines()[0][:300]
    _emit(rec, out)
    return rec


def check(shape, dtype, modules, out):
    """Largest error of out, dq, dk, dv as a share of the reference's
    largest entry, the reference being ``full_attention`` in float32."""
    from mxnet_tpu.parallel.ring import full_attention

    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    q, k, v, do = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                   for kk in keys)

    def grads(attend, cast):
        def fn(q, k, v, do):
            o, vjp = jax.vjp(attend, cast(q), cast(k), cast(v))
            return (o,) + vjp(do.astype(o.dtype))
        return [x.astype(jnp.float32) for x in jax.jit(fn)(q, k, v, do)]

    want = grads(lambda q, k, v: full_attention(q, k, v, causal=True),
                 lambda x: x.astype(jnp.float32))
    for label, module in modules:
        got = grads(lambda q, k, v: module.flash_attention(q, k, v,
                                                           causal=True),
                    lambda x: x)
        rec = {"label": "check_" + label, "shape": list(shape),
               "dtype": jnp.dtype(dtype).name,
               "platform": jax.devices()[0].platform}
        for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
            rec[name + "_rel_err"] = float(jnp.max(jnp.abs(g - w))
                                           / jnp.max(jnp.abs(w)))
        _emit(rec, out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="2,32,2048,64")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--label", default="probe")
    ap.add_argument("--parent-file", action="append", default=[],
                    help="another commit's (or a variant's) "
                         "flash_attention.py, timed beside this tree's; "
                         "may repeat")
    ap.add_argument("--blocks", default="",
                    help="block_q:block_k pins, comma-separated, tried on "
                         "this tree's file and on every --parent-file")
    ap.add_argument("--sweep", action="store_true",
                    help="explicit (block_q, block_k) over 128-2048")
    ap.add_argument("--causes", action="store_true")
    ap.add_argument("--prefill", action="store_true",
                    help="forward only at B 1, S 32-1024 (serving buckets)")
    ap.add_argument("--check", action="store_true",
                    help="forward and gradients against full_attention in "
                         "float32 on the same rounded inputs")
    ap.add_argument("--allow-cpu", action="store_true")
    a = ap.parse_args()

    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and not a.allow_cpu:
        print("flash_probe: no TPU here; a CPU timing is no device metric",
              file=sys.stderr)
        return 2
    shape = tuple(int(x) for x in a.shape.split(","))
    dtype = jnp.dtype(a.dtype)
    os.makedirs("chiprun_out/flash_probe", exist_ok=True)
    out = "chiprun_out/flash_probe/%s.jsonl" % a.label
    common = dict(on_tpu=on_tpu, out=out)
    others = [("parent:" + os.path.basename(path), _load_parent(path))
              for path in a.parent_file]
    parent = others[0][1] if others else None
    pins = [tuple(int(x) for x in pair.split(":"))
            for pair in a.blocks.split(",") if pair]

    for label, module in [("derived", fa)] + others:
        run_case(label, module, shape, dtype, **common)
        for bq, bk in pins:
            run_case(label, module, shape, dtype, block_q=bq, block_k=bk,
                     **common)
    if a.check:
        check(shape, dtype, [("derived", fa)] + others, out)

    if a.sweep:
        sizes = [x for x in (128, 256, 512, 1024, 2048) if x <= shape[2]]
        for bq in sizes:
            for bk in sizes:
                run_case("sweep", fa, shape, dtype, block_q=bq, block_k=bk,
                         **common)

    if a.causes:
        run_case("blocks_128", fa, shape, dtype, block_q=128, block_k=128,
                 **common)
        run_case("head_dim_padded", fa, shape, dtype, pad_d=True, **common)
        dot = fa._dot
        fa._dot = lambda x, y, dims: dot(x.astype(jnp.float32),
                                         y.astype(jnp.float32), dims)
        run_case("float32_operands", fa, shape, dtype, **common)
        fa._dot = dot

    if a.prefill:
        for s in (32, 64, 128, 256, 512, 1024):
            shp = (1, shape[1], s, shape[3])
            run_case("prefill", fa, shp, dtype, grad=False, iters=20,
                     **common)
            if parent:
                run_case("prefill_parent", parent, shp, dtype, grad=False,
                         iters=20, **common)
    return 0


if __name__ == "__main__":
    sys.exit(main())
