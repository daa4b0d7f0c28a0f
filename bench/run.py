"""Run one cell of the benchmark defined in ``BENCHMARK.json``.

    python3 bench/run.py --workload CELL --seed N --seconds S --trace 0|1

Run from the root of a checkout, on a machine holding the TPU chips the
cell asks for.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last ``check``: each
number the correctness check compared, beside its limit.  Off a TPU, or
on a device kind missing from ``bench/peaks.json``, it prints no result
and exits non-zero.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))

from harness.runner import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], ROOT, T_START))
