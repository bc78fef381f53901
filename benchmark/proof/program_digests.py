#!/usr/bin/env python3
"""sha256 of the StableHLO text that the serving cells' programs lower to, at
the cells' own sizes: prefill (the largest bucket the mix's prompts reach) and
the decode step of ``opt-1.3b.serve-chat`` and ``glm-5.decode-pool-16k``.
Nothing is allocated or compiled: the arguments are shapes.  A PR that edits a
model module another cell shares runs this on the parent's tree and on its own
and compares the lines.

    python3 benchmark/proof/program_digests.py            # this tree
    (cd .chip_parent && python3 benchmark/proof/program_digests.py)
"""
import hashlib
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELLS = {"opt-1.3b.serve-chat": ("mxnet_tpu.models.transformer", "TransformerConfig"),
         "glm-5.decode-pool-16k": ("mxnet_tpu.models.mla_moe", "LatentMoEConfig")}


def digests(cell_name):
    import jax
    import jax.numpy as jnp

    from benchmark import harness, weights

    cell = harness.Cell(os.path.join(ROOT, "BENCHMARK.json"), cell_name)
    module, config_cls = CELLS[cell_name]
    mod = importlib.import_module(module)
    cfg = getattr(mod, config_cls)(**cell.config["program"])
    mix = cell.traffic
    slots, page = int(mix["slots"]), int(mix["page_size"])
    pages_per_slot = min(int(mix["max_ctx"]), cfg.max_len) // page
    max_ctx = pages_per_slot * page
    bucket = page
    while bucket < min(int(mix["prompt_tokens"]["max"]), max_ctx):
        bucket *= 2
    bucket = min(bucket, max_ctx)
    # shapes alone: the transformer's table is the benchmark's own (its
    # ``init_params`` draws every weight on the host), float32 as it is served
    table, dtype = (weights.lm_shapes(cell.config["program"]), jnp.float32) \
        if not hasattr(mod, "param_shapes") else (mod.param_shapes(cfg), jnp.dtype(cfg.dtype))
    shapes = {k: jax.ShapeDtypeStruct(tuple(s), dtype) for k, (s, _kind) in table.items()}
    cache = jax.eval_shape(lambda: mod.init_kv_cache(cfg, slots * pages_per_slot, page))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    block_k = getattr(mod, "_decode_block_k", lambda *_: 0)(cfg, slots, max_ctx)
    lowered = {
        "prefill_%d" % bucket: jax.jit(mod.make_prefill_fn(cfg, page)).lower(
            shapes, cache, i32(1, bucket), i32(), i32(bucket // page)),
        "decode": jax.jit(mod.make_decode_fn(cfg, slots, pages_per_slot, page,
                                             block_k=block_k)).lower(
            shapes, cache, i32(slots), i32(slots), i32(slots, pages_per_slot),
            jax.ShapeDtypeStruct((slots,), jnp.bool_)),
    }
    return {k: hashlib.sha256(v.as_text().encode()).hexdigest() for k, v in lowered.items()}


if __name__ == "__main__":
    for name in sys.argv[1:] or sorted(CELLS):
        print(json.dumps({"cell": name, "programs": digests(name)}), flush=True)
