#!/usr/bin/env python3
"""How ``schedule_seed`` in ``benchmark/traffic/serve-chat.json`` was chosen:
the first schedule, counting from 0, that (1) keeps a stream decoding through
at least 90 % of the traced segment, so that a traced run sees the decode
program, and (2) is typical: its overlap (the mean, over every gap between two
tokens, of the streams that decode in the same step) lies between the first
and third quartiles of the overlaps that 200 seeded orders give.

    python3 benchmark/proof/serve_schedule.py

A plain model on the host, no chip: a request starts to decode 70 ms after it
is due (its prefill) and emits a token every ``STEP_S``, the decode step of the
serving cell on a TPU v5 lite (15.5-15.8 ms).
"""
import json
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import traffic_gen  # noqa: E402

STEP_S, PREFILL_S, TICK_S = 0.0158, 0.07, 0.005


def _spans(reqs):
    return [(r["due_s"] + PREFILL_S, r["due_s"] + PREFILL_S + r["answer_tokens"] * STEP_S)
            for r in reqs]


def overlap(reqs):
    t = np.arange(0.0, 40.0, TICK_S)
    n = sum(((t >= a) & (t < b)).astype(float) for a, b in _spans(reqs))
    return float((n * n).sum() / n.sum())


def traced_share(reqs, after, seconds):
    t = np.arange(after, after + seconds, TICK_S)
    on = np.zeros(len(t), bool)
    for a, b in _spans(reqs):
        on |= (t >= a) & (t < b)
    return float(on.mean())


def main():
    mix = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "serve-chat.json")))
    seconds = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["run_seconds"]
    free = {k: v for k, v in mix.items() if k != "schedule_seed"}
    seeded = [overlap(traffic_gen.open_loop_requests(free, 50272, 3145300000 + 104729 * i,
                                                     seconds)) for i in range(200)]
    q1, _, q3 = statistics.quantiles(seeded, n=4)
    print("seeded orders: overlap quartiles %.3f / %.3f, %.3f to %.3f over 200 seeds"
          % (q1, q3, min(seeded), max(seeded)))
    for s in range(100):
        reqs = traffic_gen.open_loop_requests(dict(free, schedule_seed=s), 50272, 0, seconds)
        share = traced_share(reqs, mix["trace_after_s"], mix["trace_seconds"])
        o = overlap(reqs)
        ok = share >= 0.9 and q1 <= o <= q3
        print("schedule_seed %d: traced segment %.0f %% decoding, overlap %.3f%s"
              % (s, 100 * share, o, "  <- the first that passes both" if ok else ""))
        if ok:
            return s
    return None


if __name__ == "__main__":
    main()
