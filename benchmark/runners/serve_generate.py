"""Runner for cells that serve the transformer through ``GenerateServer``.

Set-up makes the weights on the device from the seed and hands them to the
server leaf by leaf (its bind copies every parameter through the host, and the
original and the copy of all of them would not fit beside the page pool), then
sends one request through every prefill bucket the mix's prompts can land in,
which also compiles the decode step.  The window is an open loop: each request is
submitted when it is due, whether or not earlier ones have finished, and the
stream callback stamps every token with the host's clock.  Latencies count
from when a request was due, so a generator that runs late is charged to the
request, and how late it ran is printed.  After the window closes the runner
waits for every request, up to ``wait_after_s``.

``correct``: a sample of the finished requests, drawn from the seed and with
the longest in it, goes through the plain reference once each, prompt and
served tokens together, and at every served position the gap is read by
which the served token's logit lies below the reference's best.  The served
tokens are greedy, so a sound server reads the rounding of its compute type
there and nothing else: most gaps are nought, a few near-ties fall the other
way.  The numbers are the mean gap over the served tokens (``PERF.md`` says
why the widest alone does not separate a lower precision) and the widest.
"""
import threading
import time

import numpy as np

from benchmark import harness, traffic_gen, weights
from benchmark.reference import opt_lm as ref

class Run:
    def __init__(self, cell, devices, seed, tracer):
        self.cell, self.devices, self.seed, self.tracer = cell, devices, seed, tracer
        self.mix = cell.traffic
        self.model = cell.config["program"]
        self.compute_dtype = self.model["dtype"]
        self.init_std = float(cell.config.get("init_std", weights.INIT_SCALE))
        self.srv = None

    # -- set-up ---------------------------------------------------------
    def setup(self):
        import jax

        from mxnet_tpu import profiler
        from mxnet_tpu.models import transformer as tfm
        from mxnet_tpu.serving import GenerateServer

        weights.check_layout(tfm.init_params, tfm.TransformerConfig)
        self.profiler = profiler
        cfg = tfm.TransformerConfig(**self.model)
        with harness.span("bench_make_weights"):
            params = _Handover(weights.lm_params(self.model, self.seed, self.init_std))
        mix = self.mix
        self.srv = GenerateServer(
            cfg, params, slots=int(mix["slots"]), page_size=int(mix["page_size"]),
            max_ctx=int(mix["max_ctx"]), max_steps=int(mix["answer_tokens"]["max"]),
            stream_flush=int(mix["stream_flush"]), queue_depth=int(mix["queue_depth"]),
            name="bench")
        pred = self.srv.predictor
        lo, hi = int(mix["prompt_tokens"]["min"]), int(mix["prompt_tokens"]["max"])
        buckets = sorted({pred.pick_bucket(n) for n in (lo, hi)}
                         | {b for b in pred.prefill_buckets if lo <= b <= hi})
        warm = np.random.default_rng([int(self.seed), 5])
        for b in buckets:
            n = min(b, hi)
            self.srv.generate(warm.integers(0, self.model["vocab"], n, dtype=np.int32),
                              max_new_tokens=2)
        profiler.generate_reset()

    # -- the window -----------------------------------------------------
    def _counters(self):
        s = self.profiler.generate_stats()
        return {k: s.get(k, 0) for k in ("decode_steps", "slot_steps",
                                         "active_slot_steps", "tokens", "prefills")}

    def window(self, seconds):
        requests = traffic_gen.open_loop_requests(self.mix, self.model["vocab"],
                                                  self.seed, seconds)
        stamps = [[] for _ in requests]
        lock = threading.Lock()

        def on_tokens(i):
            def fn(chunk):
                with harness.span("bench_stream_callback"):
                    now = time.perf_counter()
                    with lock:
                        stamps[i].extend([now] * len(chunk))
            return fn

        def work():
            return dict(self._counters(), requests=sent)

        sent, late, futures = 0, [], []
        t0 = time.perf_counter()
        self.tracer.begin_window(t0)
        for i, r in enumerate(requests):
            while True:
                wait = t0 + r["due_s"] - time.perf_counter()
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.05))
                self.tracer.poll(work(), _no_sync)
            with harness.span("bench_submit"):
                late.append(time.perf_counter() - (t0 + r["due_s"]))
                futures.append(self.srv.submit(r["prompt"],
                                               max_new_tokens=r["answer_tokens"],
                                               stream_fn=on_tokens(i)))
            sent += 1
        while time.perf_counter() - t0 < seconds:
            time.sleep(0.02)
            self.tracer.poll(work(), _no_sync)
        self.tracer.finish(work(), _no_sync)
        closed = time.perf_counter()

        # every request is waited for; one that comes late is late, not wrong
        give_up = closed + float(self.mix["wait_after_s"])
        self.served, failed = [], 0
        for i, (r, f) in enumerate(zip(requests, futures)):
            try:
                out = f.result(timeout=max(0.0, give_up - time.perf_counter()))
                ok = len(out["tokens"]) == r["answer_tokens"] == len(stamps[i])
            except Exception as e:                      # refused, failed or never came
                print("request %d: %r" % (i, e), flush=True)
                out, ok = None, False
            failed += 0 if ok else 1
            self.served.append({"prompt": r["prompt"], "due": t0 + r["due_s"],
                                "tokens": out["tokens"] if ok else None,
                                "stamps": stamps[i]})
        self.attempted, self.failed = len(requests), failed
        miss = float(self.mix["wait_after_s"]) * 1000.0
        ttft = [1000.0 * (s["stamps"][0] - s["due"]) if s["tokens"] is not None else miss
                for s in self.served]
        gaps = [1000.0 * (b - a) for s in self.served if s["tokens"] is not None
                for a, b in zip(s["stamps"], s["stamps"][1:])]
        drained = time.perf_counter()
        done = work()
        print("generator_lateness_ms p50=%.3f p95=%.3f max=%.3f; %d requests, "
              "%d gaps between tokens, drained %.2f s after the window closed"
              % (np.percentile(late, 50) * 1e3, np.percentile(late, 95) * 1e3,
                 max(late) * 1e3, len(requests), len(gaps), drained - closed),
              flush=True)
        self.whole_window = {
            "ttft_p50_ms": float(np.percentile(ttft, 50)),
            "ttft_p95_ms": float(np.percentile(ttft, 95)),
            "slot_occupancy": done["active_slot_steps"] / max(1, done["slot_steps"]),
            "generator_lateness_p95_ms": float(np.percentile(late, 95) * 1e3)}
        return {"serve_itl_p95_ms": float(np.percentile(gaps, 95)) if gaps else miss,
                "_elapsed_s": closed - t0, "_window_start": t0, "_work": done}

    def counters(self):
        """What the whole window read on the host's clock, for the per-layer
        readers of a traced run."""
        return dict(self.whole_window)

    def release(self):
        self.srv.close()
        self.srv = None

    # -- correct --------------------------------------------------------
    def sample(self):
        """Finished requests to compare: the longest, and others drawn from
        the seed, until ``check_tokens`` served tokens are covered."""
        done = [s for s in self.served if s["tokens"] is not None]
        if not done:
            return []
        longest = max(done, key=lambda s: len(s["prompt"]) + len(s["tokens"]))
        rng = np.random.default_rng([int(self.seed), 6])
        picked, covered = [longest], len(longest["tokens"])
        for i in rng.permutation(len(done)):
            if covered >= int(self.mix["check_tokens"]):
                break
            if done[i] is not longest:
                picked.append(done[i])
                covered += len(done[i]["tokens"])
        return picked

    def reference_gaps(self, picked, quant=None):
        """Per sampled request, at each served position, how far the served
        token's reference logit lies below the reference's best.  With
        ``quant`` (the control) the token judged is the one the lower
        precision puts first, not the served one."""
        import jax
        import jax.numpy as jnp

        rows = int(self.mix["answer_tokens"]["max"])
        pad = int(self.mix["reference_pad"])       # few lengths, so few programs
        params = weights.lm_params(self.model, self.seed, self.init_std)

        def gaps(params, tokens, start, served):
            x = ref.hidden(params, tokens)[0]
            x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
            logits = ref.head(params, x)
            if quant is not None:
                xq = ref.hidden(params, tokens, quant)[0]
                xq = jax.lax.dynamic_slice_in_dim(xq, start, rows, axis=0)
                served = jnp.argmax(ref.head(params, xq, quant), axis=-1)
            at = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
            return jnp.max(logits, axis=-1) - at

        out = []
        with jax.default_matmul_precision("highest"):
            fn = jax.jit(gaps)
            for s in picked:
                seq = np.concatenate([s["prompt"], np.asarray(s["tokens"], np.int32)])
                n_prompt, n_out = len(s["prompt"]), len(s["tokens"])
                padded = min(-(-(len(seq) + rows) // pad) * pad, self.model["max_len"])
                tokens = np.zeros((1, padded), np.int32)
                tokens[0, :len(seq)] = seq
                served = np.zeros((rows,), np.int32)
                served[:n_out] = s["tokens"]
                g = fn(params, tokens, np.int32(n_prompt - 1), served)
                out.append(np.asarray(g)[:n_out])
        return out

    def check(self):
        picked = self.sample()
        limits = self.cell.limits
        checks = []
        if picked:
            gaps = np.concatenate(self.reference_gaps(picked))
            checks = [{"name": n, "value": float(v), "limit": float(limits[n])}
                      for n, v in self.compare(gaps) if n in limits]
            print("compared %d served tokens of %d requests, %d off the reference's best"
                  % (len(gaps), len(picked), int((gaps > 0).sum())), flush=True)
        return checks, self.attempted, self.failed

    @staticmethod
    def compare(gaps):
        """The numbers compared, as (name, value) pairs, from the gaps of
        all sampled served tokens."""
        return [("served_logit_gap_mean", float(gaps.mean())),
                ("served_logit_gap_max", float(gaps.max()))]


class _Handover(dict):
    """A parameter dict that gives each leaf up as ``items()`` reaches it, so
    that the reader's copy of a leaf and the original are on the device
    together for one leaf at a time and never for all of them."""

    def items(self):
        for name in list(self):
            yield name, self.pop(name)


def _no_sync():
    """The server's loop owns the device; the traced segment starts and ends
    wherever the generator happens to be."""
