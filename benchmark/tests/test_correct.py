"""What decides ``correct``, at rehearsal size on the CPU.

Three things for each kind of cell: a sound run comes out correct; the
control (the plain reference computed in the nearest precision below the one
the cell's configuration states: int8 for bfloat16, bfloat16 for float32)
comes out not correct; and a run whose timed path is broken
underneath (the harness's look for a chip skipped, the rest of the run
driven as it is) comes out not correct, once for each fault the cell can
have: a step that returns its state unchanged, half of the batch left out
with the mean taken over the rest, a token altered where it is produced.
The limits are those of ``benchmark/rehearse/workloads``; the chip's own
readings at the cells' sizes are in ``PERF.md``.
"""
import json
import os

import numpy as np
import pytest

from benchmark import harness
from benchmark import run as bench_run
from benchmark.reference import precision

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REHEARSE = os.path.join(ROOT, "benchmark", "rehearse", "BENCHMARK.json")
LM, FIT, CHAT = "lm-tiny.seq64", "resnet-tiny.fit-tiny", "lm-tiny.chat-tiny"
SEED = 2 ** 31 + 4242


def drive(cell, capsys):
    """One whole run of a rehearsal cell, in this process; its result line."""
    bench_run.main(["--rehearse", "--workload", cell, "--seed", str(SEED),
                    "--seconds", "1", "--trace", "0"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def halved(x):
    """The first half of the rows, twice: the mean is over that half alone."""
    h = x.shape[0] // 2
    return np.concatenate([np.asarray(x[:h]), np.asarray(x[:h])])


# -- the faults, planted in the program underneath the harness --------------
def lm_fault(monkeypatch, fault):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import transformer as tfm

    make = tfm.make_train_step

    def broken(*args, **kwargs):
        step, place = make(*args, **kwargs)

        def unchanged(carry, tokens):
            kept = jax.tree.map(jnp.copy, carry)
            _new, loss = step(carry, tokens)
            return kept, loss

        def half(carry, tokens):
            return step(carry, jnp.asarray(halved(tokens)))

        return {"state_unchanged": unchanged, "half_batch": half}[fault], place

    monkeypatch.setattr(tfm, "make_train_step", broken)


def fit_fault(monkeypatch, fault):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import spmd

    call = spmd.TrainStep.__call__

    def unchanged(self, carry, batch, key=None):
        kept = jax.tree.map(jnp.copy, carry)
        _new, out = call(self, carry, batch, key)
        return kept, out

    def half(self, carry, batch, key=None):
        return call(self, carry, {k: jnp.asarray(halved(v)) for k, v in batch.items()},
                    key)

    monkeypatch.setattr(spmd.TrainStep, "__call__",
                        {"state_unchanged": unchanged, "half_batch": half}[fault])


def chat_fault(monkeypatch, fault):
    from mxnet_tpu.serving import generate

    decode = generate.GenerativePredictor.decode

    def altered(self, *args, **kwargs):
        # the token is the argmax of these logits: move it one id along
        return np.roll(decode(self, *args, **kwargs), 1, axis=-1)

    assert fault == "token_altered"
    monkeypatch.setattr(generate.GenerativePredictor, "decode", altered)


FAULTS = [(LM, lm_fault, "state_unchanged"), (LM, lm_fault, "half_batch"),
          (FIT, fit_fault, "state_unchanged"), (FIT, fit_fault, "half_batch"),
          (CHAT, chat_fault, "token_altered")]


@pytest.mark.parametrize("cell", [LM, FIT, CHAT])
def test_sound_run_is_correct(cell, capsys):
    result = drive(cell, capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["compared"]
    assert result["metrics"] == {}          # a CPU run reports no device metric
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("cell,plant,fault", FAULTS,
                         ids=["%s-%s" % (c, f) for c, _p, f in FAULTS])
def test_broken_timed_path_is_not_correct(cell, plant, fault, capsys, monkeypatch):
    plant(monkeypatch, fault)
    result = drive(cell, capsys)
    assert result["correct"] is False
    over = [n for n, c in result["compared"].items() if not c["value"] <= c["limit"]]
    assert over, "no number caught the fault"


def _run(cell_name):
    cell = harness.Cell(REHEARSE, cell_name)
    runner = harness.load_module("runners", cell.traffic["runner"])
    run = runner.Run(cell, harness.require_devices(1, True), SEED,
                     harness.Tracer(False, cell_name))
    return cell, run, precision.CONTROLS[precision.CONTROL_BELOW[run.compute_dtype]]


@pytest.mark.parametrize("cell_name", [LM, FIT])
def test_training_control_is_not_correct(cell_name):
    import mxnet_tpu  # noqa: F401

    cell, run, control = _run(cell_name)
    if cell_name == FIT:
        run.setup()                         # the reference starts from fit's weights
    want = run.reference_readings()
    got = run.reference_readings(quant=control)
    checks = [{"name": n, "value": float(v), "limit": float(cell.limits[n])}
              for n, v in run.compare(got, want) if n in cell.limits]
    assert checks and not harness.judge(checks)
    # and the reference against itself is exact
    same = [{"name": n, "value": float(v), "limit": 0.0}
            for n, v in run.compare(want, want) if n in cell.limits]
    assert harness.judge(same)


def test_serving_control_is_not_correct():
    import mxnet_tpu  # noqa: F401

    cell, run, control = _run(CHAT)
    run.setup()
    run.window(1.0)
    run.release()
    picked = run.sample()

    def judged(gaps):
        return harness.judge([{"name": n, "value": v, "limit": cell.limits[n]}
                              for n, v in run.compare(np.concatenate(gaps))
                              if n in cell.limits])

    assert judged(run.reference_gaps(picked))
    assert not judged(run.reference_gaps(picked, quant=control))
