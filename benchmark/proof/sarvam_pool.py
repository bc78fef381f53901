#!/usr/bin/env python3
"""The proof scripts of the decode-pool cells with Sarvam-105B's cell in
GLM-5's place (they name their cell in a module constant, so each is loaded
here as a private copy and given the other name):

    python3 benchmark/proof/sarvam_pool.py rehearse --seed 7 --seconds 0.3 --trace 0
    python3 benchmark/proof/sarvam_pool.py readings --seeds 1 --first-seed 4200001000

``rehearse`` is ``rehearse_decode_pool.py`` on the tiny cell under
``benchmark/rehearse/decode-pool-sarvam/`` (any platform, measures nothing);
``readings`` is ``decode_pool_readings.py`` on ``sarvam-105b.decode-pool-64k``
(the chip: the program's mean logit gap and the int8 control's, each through
``harness.judge``; one JSON line a seed, appended under ``chiprun_out/``).
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BENCH = os.path.join(ROOT, "benchmark", "rehearse", "decode-pool-sarvam", "BENCHMARK.json")
TINY = "sarvam-tiny.decode-pool-tiny"
CELL = "sarvam-105b.decode-pool-64k"


def rehearsal():
    """``rehearse_decode_pool`` looking up the tiny Sarvam cell."""
    from benchmark import harness

    mod = harness.load_module("proof", "rehearse_decode_pool")
    mod.BENCH, mod.CELL = BENCH, TINY
    return mod


def main(argv):
    if argv[:1] == ["rehearse"]:
        return rehearsal().drive(argv[1:])
    if argv[:1] == ["readings"]:
        from benchmark import harness

        mod = harness.load_module("proof", "decode_pool_readings")
        mod.CELL = CELL
        sys.argv = [sys.argv[0]] + argv[1:]
        return mod.main()
    raise SystemExit(__doc__)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
