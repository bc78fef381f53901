#!/usr/bin/env python3
"""Records the small trace that ``benchmark/tests/test_program_spans.py``
reads, on the chip: one epoch of a four-batch ``Module.fit(kvstore='tpu')``
whose callback reads the metric blocking (so the program's own ``mx.fit.*``
and ``mx.metric.drain`` spans lie over real device gaps), then two calls of a
jitted step that differentiates the flash kernels under ``mx.lm.attn`` and
updates under ``mx.opt.update``.  The trace lands in
``chiprun_out/span_trace``; its ``.xplane.pb`` is copied to
``benchmark/tests/data/span_trace.xplane.pb`` by hand.  Not part of a run.
"""
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.kernels import flash_attention

    out = os.path.join(ROOT, "chiprun_out", "span_trace")
    shutil.rmtree(out, ignore_errors=True)

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=256, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.randn(512, 1024).astype(np.float32),
                           rng.randint(0, 16, 512).astype(np.float32), batch_size=128)
    mod = mx.mod.Module(net, context=mx.tpu(0))

    def fit():
        mod.fit(it, num_epoch=1, kvstore="tpu", optimizer="sgd",
                optimizer_params={"learning_rate": 0.1},
                eval_metric=mx.metric.CrossEntropy(),
                initializer=mx.initializer.Xavier(),
                batch_end_callback=lambda p: p.eval_metric.get())

    def loss(w, q):
        def layer(c, wl):
            with jax.named_scope("mx.lm.attn"):
                return flash_attention(c * wl, c, c, causal=True), None
        y, _ = jax.lax.scan(layer, q, w)
        return jnp.sum(y.astype(jnp.float32))

    @jax.jit
    def step(w, q):
        g = jax.grad(loss)(w, q)
        with jax.named_scope("mx.opt.update"):
            return w - 0.1 * g / (jnp.sqrt(g * g) + 1e-3)

    w = jnp.ones((3,), jnp.bfloat16)
    q = jnp.ones((1, 2, 256, 64), jnp.bfloat16)
    fit()
    jax.block_until_ready(step(w, q))
    it.reset()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench_traced_segment"):
        fit()
        for _ in range(2):
            w = step(w, q)
        jax.block_until_ready(w)
    jax.profiler.stop_trace()
    for dirpath, _dirs, files in os.walk(out):
        for name in files:
            path = os.path.join(dirpath, name)
            print(path, os.path.getsize(path))


if __name__ == "__main__":
    main()
