"""``repro.obs`` — tracing, metrics and online (eps, delta) accuracy
monitoring (DESIGN.md §14, docs/observability.md).

Public surface:

* :class:`Obs` / :data:`NOOP` / :func:`resolve` — the facade every
  instrumented layer threads (``ServingEngine(obs=...)``,
  ``Trainer(obs=...)``); ``None`` resolves to a no-op that records nothing.
* :mod:`repro.obs.clock` — the ONE monotonic clock behind bench timings,
  span durations and serving latencies (tests inject ``FakeClock``).
* :class:`MetricsRegistry` (counters/gauges/histograms, p50/p90/p99
  summaries, provenance-stamped JSON snapshots).
* :class:`Tracer` + :func:`chrome_trace` (JSONL spans/events, Perfetto
  export); every ``Obs.span`` is also a ``repro.<name>`` span in a JAX
  profile. :func:`kernel_scope` names the fused Pallas wrapper ops.
* :class:`DriftMonitor` — the paper's concentration bound as a live SLO.

CLI: ``python -m repro.obs {summarize,diff,chrome} trace.jsonl``.
"""
from repro.obs import clock
from repro.obs.core import NOOP, NoopObs, Obs, resolve
from repro.obs.drift import DriftMonitor, DriftReport, hoeffding_eps
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import (
    TRACE_SCHEMA,
    Tracer,
    chrome_trace,
    kernel_scope,
    read_trace,
    write_chrome,
)

__all__ = [
    "Obs", "NoopObs", "NOOP", "resolve", "clock",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "Tracer", "TRACE_SCHEMA", "chrome_trace", "read_trace", "write_chrome",
    "kernel_scope",
    "DriftMonitor", "DriftReport", "hoeffding_eps",
]
