#!/usr/bin/env python3
"""Run one benchmark cell once on the chip with a fault planted in the
program, at the cell's own size, and print the run's result line: its
``correct`` has to read false.

    python3 chipbench/tests/chip_fault.py --fault half_lanes \
        --workload qwen3-exact.decode-batch --seed 7 --seconds 51

Not part of a benchmark run; the same faults are planted at a tiny size on
the CPU by ``test_chipbench_check.py``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import builtins  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    from faults import FAULTS
    from harness import bench, loader

    bench.setup_jax()
    FAULTS[args.fault](builtins)
    out = bench.run_cell(loader.cell(args.workload), args.seed, args.seconds,
                         False, t_start=T_START)
    if out is None:
        return 3
    print(json.dumps({"fault": args.fault, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
