"""The one traffic generator: every mix is a data file of parameters, and
this module turns a mix and ``--seed`` into inputs.  The same seed gives the
same inputs; another seed gives others of the same sizes, so that the seed
never changes the amount of work.

Kinds of mix (the ``generator`` key of a traffic file):

- ``token_batches``  ``distinct_batches`` arrays of (``batch``, ``seq_len`` + 1)
  uniform token ids, for a training step that cycles them.
- ``image_batches``  ``distinct_batches`` float32 host batches of
  (``batch``, *``image_shape``) normal pixels with uniform integer labels,
  for a host-fed ``fit``.
- ``open_loop_requests``  requests for a server, sent on a schedule whether
  or not earlier ones have finished: ``rate_per_s`` x the window's seconds of
  them.  Gaps between arrivals are the quantiles of an exponential
  distribution (a Poisson process's gaps), prompt and answer lengths the
  quantiles of clipped log-normals (``median``, ``sigma``, ``min``, ``max``),
  so every seed has the same set of gaps and lengths; the seed puts each of
  the three in an order of its own and draws the prompts' token ids.
"""
import statistics

import numpy as np


def token_batches(mix, vocab, seed):
    rng = np.random.default_rng([int(seed), 1])
    shape = (int(mix["distinct_batches"]), int(mix["batch"]), int(mix["seq_len"]) + 1)
    return rng.integers(0, int(vocab), shape, dtype=np.int32)


def image_batches(mix, num_classes, seed):
    rng = np.random.default_rng([int(seed), 2])
    n, b = int(mix["distinct_batches"]), int(mix["batch"])
    data = rng.standard_normal((n, b) + tuple(mix["image_shape"]), dtype=np.float32)
    label = rng.integers(0, int(num_classes), (n, b)).astype(np.float32)
    return data, label


def _lognormal_quantiles(spec, q):
    z = np.array([statistics.NormalDist().inv_cdf(float(x)) for x in q])
    n = np.rint(float(spec["median"]) * np.exp(float(spec["sigma"]) * z))
    return np.clip(n, int(spec["min"]), int(spec["max"])).astype(np.int64)


def open_loop_requests(mix, vocab, seed, seconds):
    """[{"due_s", "prompt" (int32 array), "answer_tokens"}], by due time."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * float(seconds))))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= (n / rate) / gaps.sum()
    prompts = _lognormal_quantiles(mix["prompt_tokens"], q)
    answers = _lognormal_quantiles(mix["answer_tokens"], q)
    rng = np.random.default_rng([int(seed), 4])
    gaps = rng.permutation(gaps)
    due = np.cumsum(gaps) - gaps[0]            # the first is due as the window opens
    prompts, answers = rng.permutation(prompts), rng.permutation(answers)
    ids = rng.integers(0, int(vocab), int(prompts.sum()), dtype=np.int32)
    cuts = np.concatenate([[0], np.cumsum(prompts)])
    return [{"due_s": float(due[i]), "prompt": ids[cuts[i]:cuts[i + 1]],
             "answer_tokens": int(answers[i])} for i in range(n)]
