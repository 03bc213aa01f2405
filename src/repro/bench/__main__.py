"""CLI for the benchmark subsystem: ``python -m repro.bench``.

Modes:

* default — run the grid and write the canonical JSON artifact:
    python -m repro.bench [--quick] [--interpret] [--out BENCH_core.json]
* ``--check FILE`` — validate an artifact's schema + coverage (every
  registry estimator x every precision x >= 3 shapes) WITHOUT running
  anything; ``--against OTHER`` additionally diffs the cell grids of the
  two files. This is what the CI ``bench-core`` job gates on.
* ``--autotune`` — before timing, run the measured block-ladder autotune
  over the grid (persists winners to the shared block cache).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def _warn_if_interpret_cpu(path: str) -> None:
    """ROADMAP item 1 nag: shout when an artifact's throughput columns
    timed the Pallas INTERPRETER on CPU rather than real hardware, so an
    interpret-mode committed trajectory can't silently pass for measured
    kernel performance."""
    try:
        payload = json.loads(Path(path).read_text())
    except (OSError, ValueError):
        return
    if payload.get("kind") == "serving":
        # serving artifacts time the scheduler (often on a fake clock),
        # not Pallas kernels — the interpret nag doesn't apply
        return
    prov = payload.get("provenance", {})
    backend = prov.get("backend", payload.get("backend"))
    interpret = payload.get("interpret", prov.get("interpret"))
    if interpret and backend != "tpu":
        print(f"WARNING: {path} was produced in Pallas INTERPRET mode on "
              f"backend={backend!r} — its throughput columns time the "
              "interpreter, not hardware. Re-run the grid on a real "
              "GPU/TPU backend before reading them as the perf "
              "trajectory (ROADMAP item 1).")


def main(argv=None) -> int:
    from repro.common import env

    env.use_compile_cache()
    ap = argparse.ArgumentParser(
        prog="python -m repro.bench",
        description="unified estimator x precision x shape benchmark",
    )
    ap.add_argument("--quick", action="store_true",
                    help="small shapes / fewer repeats (CI smoke; still "
                         "full estimator x precision x >=3-shape coverage)")
    ap.add_argument("--interpret", action="store_true",
                    help="run the fused Pallas paths in interpret mode "
                         "(off-TPU CI; throughput then measures the "
                         "interpreter, read the RMSE/roofline columns)")
    ap.add_argument("--out", default="BENCH_core.json",
                    help="output artifact path (default: ./BENCH_core.json)")
    ap.add_argument("--estimators", default=None,
                    help="comma-separated registry names "
                         "(default: every registry entry)")
    ap.add_argument("--precisions", default=None,
                    help="comma-separated precision policies "
                         "(default: fp32,bf16)")
    ap.add_argument("--repeats", type=int, default=None,
                    help="timing repeats per cell")
    ap.add_argument("--bucketed", action="store_true",
                    help="also time the legacy per-degree RM baseline")
    ap.add_argument("--autotune", action="store_true",
                    help="measured block-ladder autotune before timing")
    ap.add_argument("--platform", default=None,
                    choices=["cpu", "gpu", "tpu"],
                    help="pin the jax platform before backend init "
                         "(repro.common.env.set_platform)")
    ap.add_argument("--check", metavar="FILE", default=None,
                    help="validate FILE's schema/coverage and exit")
    ap.add_argument("--against", metavar="FILE", default=None,
                    help="with --check: also diff cell coverage vs FILE")
    args = ap.parse_args(argv)

    if args.platform:
        env.set_platform(args.platform)

    from repro.bench import schema

    if args.check is not None:
        errors = schema.check_file(args.check)
        if args.against is not None:
            errors += schema.check_file(args.against)
            if not errors:
                committed = json.loads(Path(args.against).read_text())
                fresh = json.loads(Path(args.check).read_text())
                errors += schema.diff_coverage(committed, fresh)
        for path in filter(None, (args.check, args.against)):
            _warn_if_interpret_cpu(path)
        if errors:
            print(f"BENCH COVERAGE FAILURES ({args.check}):")
            for e in errors:
                print(f"  {e}")
            return 1
        print(f"bench coverage OK: {args.check}"
              + (f" (vs {args.against})" if args.against else ""))
        return 0

    import dataclasses

    from repro.bench import runner, spec as spec_mod

    spec = (spec_mod.quick_spec(interpret=args.interpret,
                                include_bucketed=args.bucketed)
            if args.quick else
            spec_mod.default_spec(interpret=args.interpret,
                                  include_bucketed=args.bucketed))
    overrides = {}
    if args.estimators:
        overrides["estimators"] = tuple(args.estimators.split(","))
    if args.precisions:
        overrides["precisions"] = tuple(args.precisions.split(","))
    if args.repeats:
        overrides["repeats"] = args.repeats
    if overrides:
        spec = dataclasses.replace(spec, **overrides)

    if args.autotune:
        runner.autotune_spec(spec, emit=print)
    payload = runner.run_spec(spec, emit=print)
    out = Path(args.out)
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {out}")
    errors = schema.check_payload(payload,
                                  estimators=spec.estimators or None,
                                  precisions=spec.precisions,
                                  min_shapes=min(3, len(spec.shapes)))
    if errors:
        print("WARNING: fresh payload fails its own coverage check:")
        for e in errors:
            print(f"  {e}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
