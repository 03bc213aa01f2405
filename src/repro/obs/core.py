"""The ``Obs`` facade: one object threading metrics + tracing + drift
monitoring through the hot paths, and a true no-op when disabled.

Every instrumented layer (``ServingEngine``, ``Scheduler``, ``Trainer``,
the launch CLIs) takes ``obs=None`` and resolves it through
:func:`resolve`: ``None`` maps to the shared :data:`NOOP` singleton, so
there are no branches at call sites and no config flags, and decode
outputs stay bit-identical because observability never touches a jax
value (tests/test_serve_obs.py pins both properties).

Every span, enabled or not, also enters
``jax.profiler.TraceAnnotation("repro." + name)``: a profile taken around
the program shows its spans on the host plane, on the clock of the device
operations. With no profiler session open the annotation records nothing
and costs one native object per span; span attributes reach it only while
a session is open.

An enabled ``Obs`` owns a :class:`~repro.obs.metrics.MetricsRegistry` and
a :class:`~repro.obs.trace.Tracer` on ONE clock (injectable — tests use
``FakeClock`` for exact lifecycle assertions), and optionally drives a
:class:`~repro.obs.drift.DriftMonitor` every ``drift_every`` ticks of the
serving/training loop.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Optional

from jax.profiler import TraceAnnotation

from repro.obs import clock as _clock
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer

__all__ = ["Obs", "NoopObs", "NOOP", "resolve"]


def _annotation(name: str, attrs: Dict[str, Any]) -> TraceAnnotation:
    """The profiler span ``repro.<name>``; ``attrs`` ride along only while
    a profiler session is open, so a closed one costs no encoding."""
    if attrs and TraceAnnotation.is_enabled():
        return TraceAnnotation("repro." + name, **attrs)
    return TraceAnnotation("repro." + name)


class NoopObs:
    """Disabled observability: nothing is recorded, ``now`` still ticks.

    ``now()`` stays a real monotonic read so engine timestamp fields keep
    their meaning whether or not observability is on; ``span`` is only the
    profiler annotation, which records nothing unless a profiler session
    is open; every other hook does nothing.
    """

    enabled = False
    drift = None

    def now(self) -> float:
        return _clock.monotonic()

    def event(self, name: str, **attrs: Any) -> None:
        pass

    def span(self, name: str, **attrs: Any):
        return _annotation(name, attrs)

    def counter(self, name: str, amount: float = 1.0) -> None:
        pass

    def gauge(self, name: str, value: float) -> None:
        pass

    def histogram(self, name: str, value: float) -> None:
        pass

    def tick_drift(self, rows=None) -> None:
        pass

    def close(self) -> None:
        pass


NOOP = NoopObs()


def resolve(obs: Optional["Obs"]) -> "Obs":
    """``None`` -> the shared no-op; anything else passes through."""
    return NOOP if obs is None else obs


class Obs:
    """Enabled observability: metrics + tracer + optional drift monitor.

    Args:
        trace_path: stream the JSONL trace here (None = in-memory only).
        clock: monotonic-clock override shared by metrics, tracer and the
            engine timestamps (tests inject ``FakeClock``).
        provenance: platform-stamp override for trace/metrics headers.
        drift: a ``DriftMonitor`` to drive from the serving/training loop.
        drift_every: run ``drift.check()`` every N ``tick_drift`` calls
            (0 disables ticking even with a monitor attached).
    """

    enabled = True

    def __init__(self, trace_path=None,
                 clock: Optional[Callable[[], float]] = None,
                 provenance: Optional[Dict] = None,
                 drift=None, drift_every: int = 0):
        self._now = clock if clock is not None else _clock.monotonic
        self.metrics = MetricsRegistry(now=self._now)
        self.tracer = Tracer(path=trace_path, now=self._now,
                             provenance=provenance)
        self.drift = drift
        self.drift_every = int(drift_every)
        self._drift_tick = 0

    # -- clock / trace / metrics passthroughs --------------------------------
    def now(self) -> float:
        return self._now()

    def event(self, name: str, **attrs: Any) -> None:
        self.tracer.event(name, **attrs)

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any):
        """A JSONL span record and the profiler span around the body."""
        with _annotation(name, attrs), self.tracer.span(name, **attrs) as t:
            yield t

    def counter(self, name: str, amount: float = 1.0) -> None:
        self.metrics.counter(name).inc(amount)

    def gauge(self, name: str, value: float) -> None:
        self.metrics.gauge(name).set(value)

    def histogram(self, name: str, value: float) -> None:
        self.metrics.histogram(name).observe(value)

    # -- drift ----------------------------------------------------------------
    def tick_drift(self, rows=None) -> None:
        """One serving/training loop tick: maybe run the drift check.

        ``rows`` (optional host array) feeds the monitor's sentinel
        reservoir before checking, so the watched points track live data.
        Emits ``drift/sup_err`` + ``drift/eps_bound`` gauges, the
        ``drift/checks``/``drift/violations`` counters, and a
        ``drift/violation`` event when the observed error leaves the
        (eps, delta) envelope.
        """
        if self.drift is None or self.drift_every <= 0:
            return
        self._drift_tick += 1
        if self._drift_tick % self.drift_every:
            return
        if rows is not None:
            self.drift.ingest(rows)
        with self.span("drift/check"):
            report = self.drift.check()
        self.gauge("drift/sup_err", report.sup_err)
        self.gauge("drift/eps_bound", report.eps_bound)
        self.counter("drift/checks")
        if not report.ok:
            self.counter("drift/violations")
            self.event("drift/violation", sup_err=report.sup_err,
                       eps_bound=report.eps_bound,
                       num_features=report.num_features)
            rec = self.drift.recommend()
            if rec is not None:
                self.gauge("drift/recommended_features",
                           rec.num_features_target)
                self.event("drift/grow_recommendation",
                           num_features_now=rec.num_features_now,
                           num_features_target=rec.num_features_target,
                           eps_bound_target=rec.eps_bound_target,
                           reason=rec.reason)

    # -- lifecycle ------------------------------------------------------------
    def write_metrics(self, path) -> None:
        self.metrics.write_json(path)

    def close(self) -> None:
        """Flush the trace file."""
        self.tracer.close()
