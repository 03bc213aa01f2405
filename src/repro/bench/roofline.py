"""Roofline rows from the dry-run artifacts (bench-subsystem home).

Reads ``results/dryrun/single/*.json`` (produced by ``python -m
repro.launch.dryrun``) and emits one row per (arch x shape):
``roofline/<arch>/<shape>,compute_us,dominant_term_seconds``. If the
dry-run hasn't been executed, emits a pointer row instead of failing (the
dry-run needs the 512-device XLA flag and ~1-2h of compiles).

``benchmarks/roofline_bench.py`` is the thin CLI over this module.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import List, Optional

__all__ = ["dryrun_roofline_rows"]


def dryrun_roofline_rows(results_dir: Optional[Path] = None) -> List[str]:
    """CSV rows derived from the compiled-program roofline terms."""
    results = (Path(results_dir) if results_dir is not None
               else Path.cwd() / "results" / "dryrun" / "single")
    rows: List[str] = []
    if not results.exists():
        return ["roofline/NOT_RUN(run repro.launch.dryrun),0,0"]
    for path in sorted(results.glob("*.json")):
        rec = json.loads(path.read_text())
        if rec.get("skipped"):
            rows.append(f"roofline/{rec['arch']}/{rec['shape']}/SKIP,0,0")
            continue
        comp = rec.get("compute_s_corrected", rec.get("compute_s", 0.0))
        dom = max(comp, rec.get("memory_s", 0), rec.get("collective_s", 0))
        rows.append(
            f"roofline/{rec['arch']}/{rec['shape']},"
            f"{comp * 1e6:.0f},{dom:.4f}"
        )
    return rows or ["roofline/EMPTY,0,0"]
