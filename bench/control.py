"""Readings that the limit of the correctness check is set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --steps 64

For each seed, in one process: the cell's set-up, its cold step and a
short window of ``--steps`` steps through the timed path, then the
check's comparison on the sampled steps twice: the program's heads
against the float32 ``highest`` reference (the reading a sound run
gives), and the control, the reference computed at ``bf16x3`` (XLA's
``high``: three bfloat16 passes, written out), against the same
reference; beside it the reference at XLA's own ``high`` precision,
which should read the same on a TPU.  One JSON line per seed.  The limit
lies between the largest program reading over a dozen seeds and the
smallest control reading.  The benchmark's own runs never run the
control.  Needs the cell's chips.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))

from harness import runner  # noqa: E402
from harness.catalog import Catalog  # noqa: E402


def readings(cat, workload, seed, devices, steps, passes):
    """Per checked step of one seed: the program's gaps, then the gaps of
    the reference at each of ``passes``."""
    run = runner.Run(cat, workload, seed, devices)
    run.setup()
    run.warmup(steps=0)
    run.window(max_steps=steps)
    run.release()
    return [[g for _, g in run.head_gaps()]] + [
        [g for _, g in run.head_gaps(p, against="reference")]
        for p in passes]


def main(argv, root=ROOT, require_chip=True):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=runner.SAMPLE_RANGE)
    args = ap.parse_args(argv)
    import jax
    cat = Catalog(root)
    runner.compile_cache(jax, cat)
    chips = cat.workload(args.workload)["chips"]
    devices = (runner.chip_devices(jax, chips, cat.peaks()) if require_chip
               else jax.devices()[:chips])
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        prog, ctrl, high = readings(cat, args.workload, seed, devices,
                                    args.steps, ("bf16x3", "high"))
        row = {"workload": args.workload, "seed": seed,
               "program": max(prog), "control": min(ctrl),
               "xla_high": min(high), "program_steps": prog,
               "control_steps": ctrl}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


if __name__ == "__main__":
    main(sys.argv[1:])
