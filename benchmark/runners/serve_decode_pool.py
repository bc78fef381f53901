"""Runner for the decode pool of a prefill/decode-split deployment: every
slot of ``GenerateServer`` holds a long stream before the window opens, and
the window sees decode steps at a full batch and nothing else.

It is ``serve_generate``'s ``Run`` (loaded by name, not edited) with another
set-up and another window.  Set-up makes the GLM-5 weights on the device in
the configuration's own type, binds them as they are, sends one request
through every prefill bucket the prompts land in (which compiles the decode
step too), resets the program's counters and then submits all ``streams``
requests together: the broker admits and prefills them one after another
before its first decode step.  Prompt lengths are the quantiles of the mix's
clipped log-normal, token ids come from the seed (the generator that is
there, ``traffic_gen.open_loop_requests``, asked for ``streams`` requests; its
arrival times are not used), and every stream is asked for the same
``answer_tokens``, long enough that none ends inside the window.

The window opens ``window_after_s`` after the last stream's first token.
``serve_itl_p95_ms`` is the 95th percentile, over all streams, of the gaps
between tokens stamped inside the window.  Then the runner waits for every
stream to end; one that ended inside it left a slot empty and counts as failed.

``correct`` is ``serve_generate``'s comparison with GLM-5's plain reference
in its place: the longest stream (and others until ``check_tokens`` served
tokens are covered) goes through ``benchmark/reference/glm5_lm.py`` once,
prompt and served tokens together, and the mean gap by which the served
token's reference logit lies below the reference's best is held to the
cell's limit.
"""
import threading
import time

import numpy as np

from benchmark import harness, traffic_gen, weights_glm5
from benchmark.reference import glm5_lm as ref
from benchmark.reference.precision import EXACT

base = harness.load_module("runners", "serve_generate")

COUNTED = ("decode_steps", "slot_steps", "active_slot_steps", "tokens", "prefills",
           "moe_pairs_held", "moe_tokens", "moe_experts_touched",
           "moe_pairs_at_max_load",
           "dsa_keys_scanned", "dsa_keys_selected")


class Run(base.Run):
    def __init__(self, cell, devices, seed, tracer):
        super().__init__(cell, devices, seed, tracer)
        self.bias_std = float(cell.config["router_bias_std"])

    def make_params(self):
        return weights_glm5.params(self.model, self.seed, self.init_std, self.bias_std)

    # -- set-up ---------------------------------------------------------
    def setup(self):
        import jax

        from mxnet_tpu import profiler
        from mxnet_tpu.models import mla_moe
        from mxnet_tpu.serving import GenerateServer

        weights_glm5.check_layout(mla_moe.param_shapes, mla_moe.LatentMoEConfig,
                                  self.model)
        self.profiler = profiler
        mix = self.mix
        cfg = mla_moe.LatentMoEConfig(**self.model)
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

    # -- the window -----------------------------------------------------
    def _counters(self):
        s = self.profiler.generate_stats()
        return {k: s.get(k, 0) for k in COUNTED}

    def window(self, seconds):
        def work():
            return dict(self._counters(), requests=len(self.requests))

        t0 = self.all_decoding + float(self.mix["window_after_s"])
        time.sleep(max(0.0, t0 - time.perf_counter()))
        t0 = time.perf_counter()
        before = work()
        self.tracer.begin_window(t0)
        while time.perf_counter() - t0 < seconds:
            time.sleep(0.02)
            self.tracer.poll(work(), base._no_sync)
        self.tracer.finish(work(), base._no_sync)
        closed = time.perf_counter()
        done = work()

        give_up = closed + float(self.mix["wait_after_s"])
        self.served, failed = [], 0
        for r, f, stamps in zip(self.requests, self.futures, self.stamps):
            try:
                out = f.result(timeout=max(0.0, give_up - time.perf_counter()))
                ok = len(out["tokens"]) == r["answer_tokens"] == len(stamps)
            except Exception as e:                      # refused, failed or never came
                print("stream: %r" % (e,), flush=True)
                out, ok = None, False
            failed += 0 if ok else 1
            self.served.append({"prompt": r["prompt"], "due": self.submitted,
                                "tokens": out["tokens"] if ok else None,
                                "stamps": stamps})
        self.attempted, self.failed = len(self.requests), failed
        miss = float(self.mix["wait_after_s"]) * 1000.0
        inside = [[t for t in s["stamps"] if t0 <= t <= closed] for s in self.served]
        gaps = [1000.0 * (b - a) for s in inside for a, b in zip(s, s[1:])]
        ended = [s["stamps"][-1] for s in self.served if s["stamps"]]
        steps = max(1, done["decode_steps"] - before["decode_steps"])
        print("%d gaps between tokens of %d streams, %d decode steps (%.3f ms a step "
              "of the window), streams ended %.2f to %.2f s after the window closed"
              % (len(gaps), len(self.served), steps, 1000.0 * (closed - t0) / steps,
                 min(ended, default=closed) - closed, max(ended, default=closed) - closed),
              flush=True)
        self.whole_window = {
            "slot_occupancy": (done["active_slot_steps"] - before["active_slot_steps"])
            / max(1, done["slot_steps"] - before["slot_steps"]),
            "streams_ended_inside": self._ended_inside(ended, closed)}
        return {"serve_itl_p95_ms": float(np.percentile(gaps, 95)) if gaps else miss,
                "_elapsed_s": closed - t0, "_window_start": t0, "_work": done}

    # -- correct --------------------------------------------------------
    def reference_gaps(self, picked, quant=None):
        """Per sampled stream, at each served position, how far the served
        token's reference logit lies below the reference's best.  With
        ``quant`` (the control) the token judged is the one the lower
        precision puts first, not the served one."""
        import jax
        import jax.numpy as jnp

        m = self.model
        params = self.make_params()
        out, t0 = [], time.perf_counter()
        with jax.default_matmul_precision("highest"):
            for s in picked:
                served = np.asarray(s["tokens"], np.int32)
                seq = jnp.asarray(np.concatenate([s["prompt"], served])[:-1])
                first = len(s["prompt"]) - 1
                logits = ref.head(params, ref.hidden(params, seq, m, EXACT)[first:])
                judged = jnp.asarray(served)
                if quant is not None:
                    low = ref.hidden(params, seq, m, quant)[first:]
                    judged = jnp.argmax(ref.head(params, low, quant), axis=-1)
                at = jnp.take_along_axis(logits, judged[:, None], axis=-1)[:, 0]
                out.append(np.asarray(jnp.max(logits, axis=-1) - at))
        print("reference: %d stream(s), %d tokens in the longest, %.1f s%s"
              % (len(picked), max(len(s["prompt"]) + len(s["tokens"]) for s in picked),
                 time.perf_counter() - t0, "" if quant is None else " with the control"),
              flush=True)
        return out

    def _ended_inside(self, ended, closed):
        """How many streams ended before the window closed.  Each left its
        slot empty from then on, so the window was no full batch: the stream
        counts as failed, and a later speed-up cannot empty slots unseen."""
        n = sum(e < closed for e in ended)
        if n:
            print("%d stream(s) ended inside the window: answer_tokens is too short "
                  "for this step" % n, flush=True)
            self.failed = min(self.attempted, self.failed + n)
        return n

    def sample(self):
        """Streams to compare: the longest, then the shortest (the two ends of
        the mix, and the two whose lengths every seed has, so the reference
        compiles no new program for them), then others drawn from the seed,
        until ``check_tokens`` served tokens are covered."""
        done = [s for s in self.served if s["tokens"] is not None]
        if not done:
            return []
        by_length = sorted(done, key=lambda s: len(s["prompt"]))
        rng = np.random.default_rng([int(self.seed), 6])
        order = [by_length[-1], by_length[0]] + [done[i] for i in rng.permutation(len(done))]
        picked, covered = [], 0
        for s in order:
            if covered >= int(self.mix["check_tokens"]):
                break
            if not any(s is p for p in picked):
                picked.append(s)
                covered += len(s["tokens"])
        return picked
