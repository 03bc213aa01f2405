#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this machine holds.

    python3 chipbench/run.py --workload qwen3-exact.decode-batch --seed 7 \
        --seconds 30 --trace 0

The cell (configuration x traffic) is looked up by name in BENCHMARK.json.
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the same window. The last
line of standard output is one JSON object; the numbers the check compared
are the last lines of standard error. With no accelerator, with fewer chips
than the cell asks for, or without the program's ``src/`` beside this
directory, it exits non-zero and prints no result.

``--control 1`` (not part of a benchmark run) also computes the control of
the check: the reference in fp8 in the program's place. ``--rate`` replaces
an open traffic's arrival rate, to find the knee.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the profiler trace in this directory")
    ap.add_argument("--rate", type=float, default=None,
                    help="open traffic: this arrival rate in place of the "
                         "traffic file's (for finding the knee)")
    args = ap.parse_args(argv)

    from harness import bench, loader

    if not (loader.ROOT / "src" / "repro").is_dir():
        print("run.py: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    cell = loader.cell(args.workload)
    if args.rate is not None:
        cell["traffic"] = dict(cell["traffic"], rate=args.rate)
    out = bench.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START, control=bool(args.control),
                         keep_trace=args.keep_trace)
    if out is None:
        return 3
    for name, v in out["compared"].items():
        bound = "<=" if name.endswith("gap") else ">="
        print(f"[compared] {name} {v['value']!r} {bound} {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
