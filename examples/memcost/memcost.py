"""Training memory cost vs rematerialization mode.

Counterpart of the reference's example/memcost/ (inception_memcost.py:
the MXNET_BACKWARD_DO_MIRROR memory/speed trade measured on a real
net). TPU-native form: the same trade is TrainStep(remat=...) — False
(save everything), "conv" (save conv/dot outputs, recompute the
elementwise tail), True (full recompute) — and the cost is read
straight from the compiled program's memory analysis instead of nvidia
-smi. The on-chip throughput side of this trade: see ROADMAP S8.
"""
import argparse

import numpy as np


def measure(remat, depth, batch, image):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet
    from mxnet_tpu.parallel.spmd import TrainStep, functional_optimizer

    sym = resnet.get_symbol(num_classes=10, num_layers=depth,
                            image_shape=image)
    ts = TrainStep(sym, functional_optimizer("sgd", learning_rate=0.1),
                   mesh=None, remat=remat)
    shapes = {"data": (batch,) + image, "softmax_label": (batch,)}
    params, opt_state, aux = ts.init_params(
        shapes, initializer=mx.initializer.Xavier())
    carry = ts.place(params, opt_state, aux)
    rng = np.random.RandomState(0)
    b = {"data": rng.randn(*shapes["data"]).astype(np.float32),
         "softmax_label": rng.randint(0, 10, batch).astype(np.float32)}
    key = jax.random.PRNGKey(0)
    fn = ts.compile(*carry[:3])
    compiled = fn.lower(carry, b, key).compile()
    ma = compiled.memory_analysis()
    return dict(temp=ma.temp_size_in_bytes,
                args=ma.argument_size_in_bytes,
                output=ma.output_size_in_bytes)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--depth", type=int, default=20)
    p.add_argument("--batch-size", type=int, default=32)
    args = p.parse_args()
    image = (3, 32, 32)

    rows = []
    for remat in (False, "conv", True):
        m = measure(remat, args.depth, args.batch_size, image)
        rows.append((remat, m))
        print("remat=%-6s temp=%8.2f MB  args=%7.2f MB  out=%7.2f MB"
              % (remat, m["temp"] / 2**20, m["args"] / 2**20,
                 m["output"] / 2**20))

    base = rows[0][1]["temp"]
    conv = rows[1][1]["temp"]
    full = rows[2][1]["temp"]
    # conv-remat drops the saved elementwise tail (BN-apply/ReLU) from
    # the residual set. Full recompute is NOT automatically a peak win:
    # the backward re-materializes activations, and whether peak falls
    # depends on how the scheduler interleaves recompute with consume
    # (the round-5 chip record, ROADMAP S8: on the ResNet graph it
    # costs speed, i.e. it is a memory lever for memory-LIMITED
    # models, not a default).
    print("conv-remat temp: %.3fx of no-remat" % (conv / base))
    print("full-remat temp: %.3fx of no-remat" % (full / base))
    print("memcost ok: %s" % (conv <= base))


if __name__ == "__main__":
    main()
