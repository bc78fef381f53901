#!/usr/bin/env python3
"""Why prefill's selection is ``mla_moe._largest_k`` (a bisection, no sort)
while decode's is ``lax.top_k``: the time of each on one row block of the
16384-token prefill bucket, (256, 16384) float32 scores, 2048 kept, as a
mask.  Decode needs the indices (it gathers the kept rows), prefill only the
mask.  Both give the same set (``tests/test_mla_moe.py``).  Not part of a run.

    python3 benchmark/proof/largest_k_probe.py
"""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(rows=256, keys=16384, k=2048, calls=20):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.models import mla_moe

    def by_top_k(scores):
        _top, idx = lax.top_k(scores, k)
        return jnp.zeros(scores.shape, bool).at[jnp.arange(rows)[:, None], idx].set(True)

    scores = jax.random.normal(jax.random.PRNGKey(0), (rows, keys), jnp.float32)
    fns = {"largest_k": jax.jit(lambda s: mla_moe._largest_k(s, k)),
           "top_k_mask": jax.jit(by_top_k)}
    masks = {n: jax.block_until_ready(f(scores)) for n, f in fns.items()}
    print("same set:", bool(jnp.array_equal(*masks.values())), flush=True)
    for name, fn in fns.items():
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(scores)
        jax.block_until_ready(out)
        print("%s: %.3f ms a (%d, %d) block on %s" % (
            name, 1e3 * (time.perf_counter() - t0) / calls, rows, keys,
            jax.devices()[0].device_kind), flush=True)


if __name__ == "__main__":
    main()
