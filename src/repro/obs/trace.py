"""Span tracing: JSONL events, Chrome-trace export, kernel-launch scopes.

A :class:`Tracer` records two record kinds on the shared monotonic clock
(``repro.obs.clock``), streamed to a ``.jsonl`` file when a path is given
and always kept in memory::

    {"type": "meta",  "schema": ..., "provenance": {...}, "wall_time": ...}
    {"type": "span",  "name": ..., "ts_us": ..., "dur_us": ..., "attrs": {}}
    {"type": "event", "name": ..., "ts_us": ...,               "attrs": {}}

The first line of every trace file is the ``meta`` record (schema version +
platform provenance), which is what ``tools/check_trace.py`` validates and
``python -m repro.obs`` summarizes/diffs. :func:`chrome_trace` converts a
record list to the Chrome ``traceEvents`` format, so any trace opens in
Perfetto / ``chrome://tracing`` (spans become complete "X" slices, events
instant "i" marks).

The fused Pallas wrapper ops run under :func:`kernel_scope`, a
``jax.named_scope``: device profiles and HLO dumps carry the kernel name.
"""
from __future__ import annotations

import contextlib
import json
from pathlib import Path
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax

from repro.obs import clock as _clock

__all__ = ["TRACE_SCHEMA", "Tracer", "kernel_scope", "chrome_trace",
           "read_trace", "write_chrome"]

TRACE_SCHEMA = "repro.obs.trace/v1"


class Tracer:
    """Append-only span/event recorder on the shared monotonic clock.

    Args:
        path: optional ``.jsonl`` destination — records stream to it as
            they are recorded (the meta header first), so a crashed run
            still leaves a readable trace.
        now: clock override (tests inject ``FakeClock``).
        provenance: platform stamp override for the meta record.
    """

    def __init__(self, path=None,
                 now: Callable[[], float] = _clock.monotonic,
                 provenance: Optional[Dict] = None):
        self._now = now
        self.records: List[Dict] = []
        self._fh = None
        if path is not None:
            p = Path(path)
            p.parent.mkdir(parents=True, exist_ok=True)
            self._fh = p.open("w")
        if provenance is None:
            from repro.common.env import platform_provenance

            provenance = platform_provenance()
        self._emit({"type": "meta", "schema": TRACE_SCHEMA,
                    "wall_time": _clock.wall(), "provenance": provenance})

    # -- recording ----------------------------------------------------------
    def _emit(self, rec: Dict) -> None:
        self.records.append(rec)
        if self._fh is not None:
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()

    def now_us(self) -> float:
        return self._now() * 1e6

    def event(self, name: str, **attrs: Any) -> None:
        """Record an instantaneous event."""
        self._emit({"type": "event", "name": name, "ts_us": self.now_us(),
                    "attrs": attrs})

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any):
        """Record a duration span around the ``with`` body."""
        t0 = self.now_us()
        try:
            yield self
        finally:
            t1 = self.now_us()
            self._emit({"type": "span", "name": name, "ts_us": t0,
                        "dur_us": t1 - t0, "attrs": attrs})

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    # -- convenience --------------------------------------------------------
    def spans(self, name: Optional[str] = None) -> List[Dict]:
        out = [r for r in self.records if r["type"] == "span"]
        return out if name is None else [r for r in out if r["name"] == name]

    def events(self, name: Optional[str] = None) -> List[Dict]:
        out = [r for r in self.records if r["type"] == "event"]
        return out if name is None else [r for r in out if r["name"] == name]


def kernel_scope(kernel: str):
    """Name a fused-kernel launch: the HLO operations produced inside carry
    ``kernel`` in their metadata, so device profiles group by kernel
    family."""
    return jax.named_scope(kernel)


# ---------------------------------------------------------------------------
# file IO + Chrome-trace conversion
# ---------------------------------------------------------------------------
def read_trace(path) -> List[Dict]:
    """Load a ``.jsonl`` trace file into a record list."""
    records = []
    for line in Path(path).read_text().splitlines():
        line = line.strip()
        if line:
            records.append(json.loads(line))
    return records


def chrome_trace(records: Iterable[Dict]) -> Dict:
    """Convert obs records to the Chrome ``traceEvents`` JSON format.

    Spans become complete ("ph": "X") slices and events instant ("ph": "i")
    marks, all on one pid/tid; ``attrs`` ride along as ``args``. The meta
    record maps to process metadata.
    """
    out: List[Dict] = []
    for rec in records:
        if rec.get("type") == "meta":
            out.append({"name": "process_name", "ph": "M", "pid": 0,
                        "args": {"name": "repro.obs "
                                 + str(rec.get("provenance", {}))}})
        elif rec.get("type") == "span":
            out.append({"name": rec["name"], "ph": "X", "pid": 0, "tid": 0,
                        "ts": rec["ts_us"], "dur": rec.get("dur_us", 0.0),
                        "args": rec.get("attrs", {})})
        elif rec.get("type") == "event":
            out.append({"name": rec["name"], "ph": "i", "pid": 0, "tid": 0,
                        "ts": rec["ts_us"], "s": "g",
                        "args": rec.get("attrs", {})})
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def write_chrome(records: Iterable[Dict], path) -> Path:
    """Write the Chrome-trace conversion of ``records`` to ``path``."""
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(chrome_trace(records)) + "\n")
    return p
