#!/usr/bin/env python3
"""Records the small trace that ``benchmark/tests/test_trace_reduce.py``
reads, on the chip: three calls of a jitted function that scans three matrix
products and runs the flash forward kernel once, each under the benchmark's
own host spans.  The trace lands in ``chiprun_out/tiny_trace``; its
``.xplane.pb`` is copied to ``benchmark/tests/data/tiny_trace.xplane.pb`` by
hand.  Not part of a run.
"""
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    import jax
    import jax.numpy as jnp

    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.kernels import flash_attention

    out = os.path.join(ROOT, "chiprun_out", "tiny_trace")
    shutil.rmtree(out, ignore_errors=True)

    @jax.jit
    def f(x, q):
        y, _ = jax.lax.scan(lambda c, _: (jnp.tanh(c @ c), None), x, None, length=3)
        return y, flash_attention(q, q, q, causal=True)

    x = jnp.ones((256, 256), jnp.bfloat16)
    q = jnp.ones((1, 2, 256, 64), jnp.bfloat16)
    jax.block_until_ready(f(x, q))
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench_traced_segment"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench_train_step_dispatch"):
                r = f(x, q)
            with jax.profiler.TraceAnnotation("bench_wait_device"):
                jax.block_until_ready(r)
                time.sleep(0.002)
    jax.profiler.stop_trace()
    for dirpath, _dirs, files in os.walk(out):
        for name in files:
            path = os.path.join(dirpath, name)
            print(path, os.path.getsize(path))


if __name__ == "__main__":
    main()
