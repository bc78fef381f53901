"""Runner for the decode pool of a LongCat-Flash deployment: ``serve_decode_pool``'s
``Run`` (loaded by name, not edited) with the shortcut-connected double-block
program (``mxnet_tpu/models/scmoe.py``), its seeded weights
(``benchmark/weights_longcat.py``) and its plain reference
(``benchmark/reference/longcat_lm.py``) in the places of GLM-5's.

The window, the sample and the comparison that decides ``correct`` are the
parent's own methods.  ``setup`` is the parent's with the three names swapped
(the parent's names its model module inside the function, so it is repeated
here); ``reference_gaps`` finds the reference as the module's ``ref``, and this
module's private copy of the parent module is given LongCat's.
"""
import threading
import time

import numpy as np

from benchmark import harness, traffic_gen, weights_longcat
from benchmark.reference import longcat_lm

pool = harness.load_module("runners", "serve_decode_pool")   # a copy of its own
pool.ref = longcat_lm

COUNTED = ("decode_steps", "slot_steps", "active_slot_steps", "tokens", "prefills",
           "moe_pairs_held", "moe_tokens", "moe_experts_touched",
           "moe_pairs_at_max_load", "moe_pairs_zero", "attn_rows_read")


class Run(pool.Run):
    def make_params(self):
        return weights_longcat.params(self.model, self.seed, self.init_std, self.bias_std)

    def _counters(self):
        s = self.profiler.generate_stats()
        return {k: s.get(k, 0) for k in COUNTED}

    def setup(self):
        import jax

        from mxnet_tpu import profiler
        from mxnet_tpu.models import scmoe
        from mxnet_tpu.serving import GenerateServer

        weights_longcat.check_layout(scmoe.param_shapes, scmoe.ShortcutMoEConfig,
                                     self.model)
        self.profiler = profiler
        mix = self.mix
        cfg = scmoe.ShortcutMoEConfig(**self.model)
        clock = [time.perf_counter()]

        def lap(what):
            clock.append(time.perf_counter())
            print("set-up: %s %.1f s" % (what, clock[-1] - clock[-2]), flush=True)

        with harness.span("bench_make_weights"):
            params = self.make_params()
            jax.block_until_ready(params)
        lap("weights")
        self.srv = GenerateServer(
            cfg, params, slots=int(mix["slots"]), page_size=int(mix["page_size"]),
            max_ctx=int(mix["max_ctx"]), max_steps=int(mix["answer_tokens"]),
            stream_flush=int(mix["stream_flush"]), queue_depth=int(mix["queue_depth"]),
            name="bench")
        del params
        n = int(mix["streams"])
        answers = {"median": mix["answer_tokens"], "sigma": 0.0,
                   "min": mix["answer_tokens"], "max": mix["answer_tokens"]}
        self.requests = traffic_gen.open_loop_requests(
            dict(mix, rate_per_s=1.0, answer_tokens=answers), self.model["vocab"],
            self.seed, n)
        pred = self.srv.predictor
        warm = np.random.default_rng([int(self.seed), 5])
        for b in sorted({pred.pick_bucket(len(r["prompt"])) for r in self.requests}):
            longest = max(len(r["prompt"]) for r in self.requests
                          if pred.pick_bucket(len(r["prompt"])) == b)
            self.srv.generate(warm.integers(0, self.model["vocab"], longest,
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
