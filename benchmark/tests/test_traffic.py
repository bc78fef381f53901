"""The traffic generator: the same seed gives the same inputs, another seed
gives others of the same sizes (with a ``schedule_seed``, the same requests at
the same times), and a seed past 2**31 is taken."""
import glob
import os

import numpy as np
import pytest

from benchmark import harness, traffic_gen

BIG = 2 ** 31 + 12345
CHAT = {"rate_per_s": 4.0,
        "prompt_tokens": {"median": 256, "sigma": 1.0, "min": 32, "max": 1024},
        "answer_tokens": {"median": 64, "sigma": 0.7, "min": 16, "max": 192}}


def _arrays(kind, seed):
    if kind == "token_batches":
        return [traffic_gen.token_batches(
            {"distinct_batches": 4, "batch": 2, "seq_len": 64}, 50272, seed)]
    if kind == "image_batches":
        return list(traffic_gen.image_batches(
            {"distinct_batches": 2, "batch": 4, "image_shape": [3, 8, 8]}, 1000, seed))
    reqs = traffic_gen.open_loop_requests(CHAT, 50272, seed, 20)
    return [np.array([r["due_s"] for r in reqs]),
            np.concatenate([r["prompt"] for r in reqs]),
            np.array([r["answer_tokens"] for r in reqs])]


@pytest.mark.parametrize("kind", ["token_batches", "image_batches", "open_loop_requests"])
def test_same_seed_same_inputs_other_seed_others(kind):
    a, b, c = _arrays(kind, BIG), _arrays(kind, BIG), _arrays(kind, BIG + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert all(x.shape == y.shape for x, y in zip(a, c))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))


def test_every_seed_has_the_same_set_of_sizes_and_gaps():
    one = traffic_gen.open_loop_requests(CHAT, 50272, 1, 20)
    two = traffic_gen.open_loop_requests(CHAT, 50272, BIG, 20)
    for key in (lambda r: len(r["prompt"]), lambda r: r["answer_tokens"]):
        assert sorted(map(key, one)) == sorted(map(key, two))
    gaps = [np.sort(np.diff([r["due_s"] for r in x])) for x in (one, two)]
    assert len(one) == 80 and one[0]["due_s"] == 0.0
    assert np.allclose(np.sort(np.append(gaps[0], 0))[1:].sum(), gaps[1].sum(), rtol=0.05)
    assert all(0.0 <= r["due_s"] < 20.0 for r in one + two)


def test_a_schedule_seed_gives_every_seed_the_same_requests_at_the_same_times():
    """The seed then draws the token ids alone: as many streams overlap, and
    every step costs the same, in every run."""
    mix = dict(CHAT, schedule_seed=2)
    one = traffic_gen.open_loop_requests(mix, 50272, 1, 20)
    two = traffic_gen.open_loop_requests(mix, 50272, BIG, 20)
    shape = [[(r["due_s"], len(r["prompt"]), r["answer_tokens"]) for r in x] for x in (one, two)]
    assert shape[0] == shape[1]
    assert not all(np.array_equal(a["prompt"], b["prompt"]) for a, b in zip(one, two))
    free = traffic_gen.open_loop_requests(CHAT, 50272, 1, 20)
    for i, key in ((1, lambda r: len(r["prompt"])), (2, lambda r: r["answer_tokens"])):
        assert sorted(s[i] for s in shape[0]) == sorted(map(key, free))
    serve = harness.load_json(os.path.join(harness.HERE, "traffic", "serve-chat.json"))
    assert isinstance(serve["schedule_seed"], int)


def test_lengths_follow_the_mix():
    reqs = traffic_gen.open_loop_requests(CHAT, 50272, 7, 50)
    prompts = [len(r["prompt"]) for r in reqs]
    answers = [r["answer_tokens"] for r in reqs]
    assert min(prompts) >= 32 and max(prompts) == 1024
    assert min(answers) >= 16 and max(answers) == 192
    assert np.median(prompts) == pytest.approx(256, rel=0.03)
    assert np.median(answers) == pytest.approx(64, rel=0.03)
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 50272 for r in reqs)


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(harness.HERE, "traffic", "decode-pool-*.json"))), ids=os.path.basename)
def test_no_decode_pool_stream_ends_inside_the_window_above_a_step_of_7_82_ms(path):
    """Every stream starts to decode at the first step after the last prefill,
    and the window closes ``window_after_s`` + ``run_seconds`` after that; a
    stream that ends before it counts as failed.  So the answer is long enough
    that a decode step may fall to 7.82 ms before a faster program empties a
    slot inside the window; and ``correct`` compares two whole answers, the
    longest stream's and the shortest's."""
    mix = harness.load_json(path)
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    window = float(mix["window_after_s"]) + float(bench["run_seconds"])
    assert (int(mix["answer_tokens"]) - 1) * 7.82e-3 >= window
    assert int(mix["check_tokens"]) >= 2 * int(mix["answer_tokens"])
