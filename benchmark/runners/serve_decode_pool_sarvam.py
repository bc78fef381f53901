"""Runner for the decode pool of a Sarvam-105B deployment: ``serve_decode_pool``'s
``Run`` (loaded by name, not edited) with ``mxnet_tpu/models/mla_moe.py`` as
the configuration sets it (a full-rank query, q and k norms, DeepSeek YaRN,
no indexer), its seeded weights (``benchmark/weights_sarvam.py``) and its plain
reference (``benchmark/reference/sarvam_lm.py``) in the places of GLM-5's.

The window, the sample and the comparison that decides ``correct`` are the
parent's own methods.  ``setup`` is the parent's with the three names swapped
and one change: the page pool is sized in bytes (``pool_bytes``) to what the
streams hold, every prompt and answer in whole pages and a page a slot, times
``1 + pool_margin``, not ``slots x max_ctx``; and one request goes through each
prefill bucket with the shortest prompt of the mix that lands in it (the
program is the bucket's whatever the length).  ``reference_gaps`` finds the
reference as the module's ``ref``, and this module's private copy of the
parent module is given Sarvam's.
"""
import threading
import time

import numpy as np

from benchmark import harness, traffic_gen, weights_sarvam
from benchmark.reference import sarvam_lm

pool = harness.load_module("runners", "serve_decode_pool")   # a copy of its own
pool.ref = sarvam_lm

COUNTED = ("decode_steps", "slot_steps", "active_slot_steps", "tokens", "prefills",
           "moe_pairs_held", "moe_tokens", "moe_experts_touched",
           "moe_pairs_at_max_load", "attn_rows_read")


def pool_pages(prompts, answer, page, slots, margin):
    """Pages that hold every stream's prompt and answer, a page a slot, and
    ``margin`` more."""
    held = sum(-(-(int(n) + int(answer)) // int(page)) for n in prompts) + int(slots)
    return int(np.ceil(held * (1.0 + float(margin))))


class Run(pool.Run):
    def make_params(self):
        return weights_sarvam.params(self.model, self.seed, self.init_std, self.bias_std)

    def _counters(self):
        s = self.profiler.generate_stats()
        return {k: s.get(k, 0) for k in COUNTED}

    def setup(self):
        import jax

        from mxnet_tpu import profiler
        from mxnet_tpu.models import mla_moe
        from mxnet_tpu.serving import GenerateServer

        weights_sarvam.check_layout(mla_moe.param_shapes, mla_moe.LatentMoEConfig,
                                    self.model)
        self.profiler = profiler
        mix = self.mix
        cfg = mla_moe.LatentMoEConfig(**self.model)
        clock = [time.perf_counter()]

        def lap(what):
            clock.append(time.perf_counter())
            print("set-up: %s %.1f s" % (what, clock[-1] - clock[-2]), flush=True)

        n = int(mix["streams"])
        answers = {"median": mix["answer_tokens"], "sigma": 0.0,
                   "min": mix["answer_tokens"], "max": mix["answer_tokens"]}
        self.requests = traffic_gen.open_loop_requests(
            dict(mix, rate_per_s=1.0, answer_tokens=answers), self.model["vocab"],
            self.seed, n)
        page = int(mix["page_size"])
        pages = pool_pages([len(r["prompt"]) for r in self.requests], mix["answer_tokens"],
                           page, mix["slots"], mix["pool_margin"])
        pool_bytes = pages * mla_moe.kv_page_bytes(cfg, page)
        with harness.span("bench_make_weights"):
            params = self.make_params()
            jax.block_until_ready(params)
        lap("weights")
        self.srv = GenerateServer(
            cfg, params, slots=int(mix["slots"]), page_size=page,
            max_ctx=int(mix["max_ctx"]), pool_bytes=pool_bytes,
            max_steps=int(mix["answer_tokens"]), stream_flush=int(mix["stream_flush"]),
            queue_depth=int(mix["queue_depth"]), name="bench")
        del params
        pred = self.srv.predictor
        print("page pool: %d pages of %d tokens, %.3f GB (slots x max_ctx would be "
              "%.3f GB)" % (pred.pool.num_pages, page, pool_bytes / 1e9,
                            int(mix["slots"]) * pred.max_pages_per_slot
                            * pred.page_bytes / 1e9), flush=True)
        warm = np.random.default_rng([int(self.seed), 5])
        for b in sorted({pred.pick_bucket(len(r["prompt"])) for r in self.requests}):
            shortest = min(len(r["prompt"]) for r in self.requests
                           if pred.pick_bucket(len(r["prompt"])) == b)
            self.srv.generate(warm.integers(0, self.model["vocab"], shortest,
                                            dtype=np.int32), max_new_tokens=2)
        lap("one request through each prefill bucket and the decode step")
        profiler.generate_reset()

        self.stamps = [[] for _ in self.requests]
        lock = threading.Lock()

        def on_tokens(i):
            def fn(chunk):
                now = time.perf_counter()
                with lock:
                    self.stamps[i].extend([now] * len(chunk))
            return fn

        self.submitted = time.perf_counter()
        with harness.span("bench_submit"):
            self.futures = [self.srv.submit(r["prompt"], max_new_tokens=r["answer_tokens"],
                                            stream_fn=on_tokens(i))
                            for i, r in enumerate(self.requests)]
        give_up = self.submitted + float(mix["prefill_wait_s"])
        while not all(self.stamps) and time.perf_counter() < give_up \
                and not any(f.done() for f in self.futures):
            time.sleep(0.01)
        self.all_decoding = max((s[0] for s in self.stamps if s), default=self.submitted)
        print("streams=%d prompts %d-%d tokens (%d in all), all decoding %.2f s "
              "after they were submitted"
              % (n, min(len(r["prompt"]) for r in self.requests),
                 max(len(r["prompt"]) for r in self.requests),
                 sum(len(r["prompt"]) for r in self.requests),
                 self.all_decoding - self.submitted), flush=True)
