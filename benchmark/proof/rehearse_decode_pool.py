#!/usr/bin/env python3
"""The CPU rehearsal of ``benchmark/runners/serve_decode_pool.py``: one whole
run of ``benchmark/run.py --rehearse`` on the tiny cell under
``benchmark/rehearse/decode-pool/`` (a ``BENCHMARK.json`` of its own beside its
configs, traffic and limits), which measures nothing.

    python3 benchmark/proof/rehearse_decode_pool.py --seed 7 --seconds 0.3 --trace 0
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BENCH = os.path.join(ROOT, "benchmark", "rehearse", "decode-pool", "BENCHMARK.json")
CELL = "glm-tiny.decode-pool-tiny"


def drive(argv):
    """``run.py --rehearse`` with the cell looked up in ``BENCH``."""
    from benchmark import harness
    from benchmark import run as bench_run

    cell = harness.Cell
    harness.Cell = lambda _path, workload: cell(BENCH, workload)
    try:
        return bench_run.main(["--rehearse", "--workload", CELL] + list(argv))
    finally:
        harness.Cell = cell


if __name__ == "__main__":
    sys.exit(drive(sys.argv[1:]))
