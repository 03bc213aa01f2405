"""Unit coverage for the repro.obs layer: metrics percentiles, JSONL trace
round-trips, Chrome export, the no-op fast path, provenance stamps, and the
(eps, delta) drift monitor firing exactly when it should."""
import json

import numpy as np
import pytest

from repro.obs import (
    NOOP,
    DriftMonitor,
    MetricsRegistry,
    Obs,
    Tracer,
    chrome_trace,
    clock,
    hoeffding_eps,
    kernel_scope,
    read_trace,
    resolve,
)
from repro.obs.metrics import percentile

PROV = {"backend": "test", "device_kind": "test", "device_count": 1,
        "interpret": False, "jax_version": "0"}


# ---------------------------------------------------------------------------
# clock
# ---------------------------------------------------------------------------
def test_fake_clock_is_deterministic():
    fc = clock.FakeClock(start=10.0, step=0.5)
    assert [fc(), fc()] == [10.0, 10.5]
    fc.advance(4.0)
    assert fc() == 15.0


def test_real_clock_monotonic():
    a, b = clock.monotonic(), clock.monotonic()
    assert b >= a


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def test_percentiles_exact_on_small_sets():
    vals = sorted(float(v) for v in range(101))  # 0..100
    assert percentile(vals, 50.0) == 50.0
    assert percentile(vals, 99.0) == 99.0
    assert percentile([], 50.0) == 0.0
    assert percentile([7.0], 90.0) == 7.0


def test_histogram_summary_and_snapshot():
    reg = MetricsRegistry(now=clock.FakeClock())
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.histogram("serve/ttft_s").observe(v)
    reg.counter("serve/requests_submitted").inc(3)
    reg.gauge("serve/queue_depth").set(2)

    snap = reg.snapshot(provenance=PROV)
    assert snap["schema"] == "repro.obs.metrics/v1"
    assert snap["provenance"] == PROV
    assert snap["counters"]["serve/requests_submitted"] == 3.0
    assert snap["gauges"]["serve/queue_depth"] == 2.0
    h = snap["histograms"]["serve/ttft_s"]
    assert h["count"] == 4 and h["mean"] == 2.5
    assert h["min"] == 1.0 and h["max"] == 4.0
    assert h["p50"] == 3.0  # nearest-rank on [1,2,3,4]
    # JSON-able end to end
    json.dumps(snap)


def test_histogram_reservoir_keeps_exact_count():
    from repro.obs import metrics as m

    reg = MetricsRegistry(now=clock.FakeClock())
    h = reg.histogram("x")
    n = m._RESERVOIR + 500
    for v in range(n):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == n and s["min"] == 0.0 and s["max"] == n - 1
    assert len(h._vals) == m._RESERVOIR


def test_write_json(tmp_path):
    reg = MetricsRegistry(now=clock.FakeClock())
    reg.counter("c").inc()
    p = reg.write_json(tmp_path / "m.json", provenance=PROV)
    assert json.loads(p.read_text())["counters"]["c"] == 1.0


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------
def test_tracer_jsonl_roundtrip(tmp_path):
    path = tmp_path / "t.jsonl"
    tr = Tracer(path=path, now=clock.FakeClock(), provenance=PROV)
    tr.event("request/submit", request_id=0)
    with tr.span("prefill", bucket=32):
        pass
    tr.close()

    recs = read_trace(path)
    assert recs == tr.records
    assert recs[0]["type"] == "meta"
    assert recs[0]["schema"] == "repro.obs.trace/v1"
    assert recs[0]["provenance"] == PROV
    (ev,) = [r for r in recs if r["type"] == "event"]
    assert ev["name"] == "request/submit" and ev["attrs"]["request_id"] == 0
    (sp,) = [r for r in recs if r["type"] == "span"]
    # FakeClock(step=1): event reads t=0 -> ts 0us? meta takes no read;
    # event read 0.0, span start 1.0, span end 2.0
    assert sp["ts_us"] == 1e6 and sp["dur_us"] == 1e6
    assert sp["attrs"] == {"bucket": 32}


def test_tracer_span_records_on_exception():
    tr = Tracer(now=clock.FakeClock(), provenance=PROV)
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    assert len(tr.spans("boom")) == 1


def test_chrome_trace_shapes():
    tr = Tracer(now=clock.FakeClock(), provenance=PROV)
    tr.event("e")
    with tr.span("s"):
        pass
    chrome = chrome_trace(tr.records)
    phases = [e["ph"] for e in chrome["traceEvents"]]
    assert phases == ["M", "i", "X"]
    assert all("ts" in e for e in chrome["traceEvents"][1:])


def test_kernel_scope_names_the_scope():
    """The fused wrapper ops' scope reaches the HLO metadata."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with kernel_scope("rm_feature"):
            return jnp.sin(x) * 2.0

    hlo = jax.jit(f).lower(jnp.ones((4, 8))).as_text(debug_info=True)
    assert "rm_feature/" in hlo


def _profiled(tmp_path, body):
    """Run ``body`` under a JAX profiler session on the CPU; the host
    events of the profile as ``{name: [(start_ns, end_ns, stats)]}``."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    out = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)))
    return out


def test_spans_reach_the_profiler(tmp_path):
    """Disabled and enabled spans both land on the profile's host plane as
    ``repro.<name>``, nested as they ran, attributes as event stats; the
    enabled Obs still keeps its JSONL record."""
    obs = Obs(clock=clock.FakeClock(), provenance=PROV)

    def body():
        with NOOP.span("outer", slot=3):
            with obs.span("inner", bucket=32):
                pass

    events = _profiled(tmp_path, body)
    ((o0, o1, o_stats),) = events["repro.outer"]
    ((i0, i1, i_stats),) = events["repro.inner"]
    assert o0 <= i0 <= i1 <= o1
    assert o_stats == {"slot": 3} and i_stats == {"bucket": 32}
    (sp,) = obs.tracer.spans("inner")
    assert sp["attrs"] == {"bucket": 32}
    obs.close()


# ---------------------------------------------------------------------------
# facade / no-op path
# ---------------------------------------------------------------------------
def test_resolve_none_is_shared_noop():
    assert resolve(None) is NOOP
    obs = Obs(clock=clock.FakeClock(), provenance=PROV)
    assert resolve(obs) is obs
    obs.close()


def test_noop_is_inert():
    assert NOOP.enabled is False
    NOOP.event("x", a=1)
    NOOP.counter("c")
    NOOP.gauge("g", 1.0)
    NOOP.histogram("h", 1.0)
    NOOP.tick_drift()
    with NOOP.span("s", a=1):
        pass
    # a span is only the profiler's annotation, which records nothing
    # unless a profiler session is open
    import jax

    assert isinstance(NOOP.span("a"), jax.profiler.TraceAnnotation)
    assert NOOP.now() <= NOOP.now()


def test_obs_shares_one_clock():
    fc = clock.FakeClock()
    obs = Obs(clock=fc, provenance=PROV)
    t0 = obs.now()
    obs.histogram("h", 1.0)          # one clock read inside observe
    with obs.span("s"):
        pass                         # two reads
    t1 = obs.now()
    assert t1 - t0 == 4.0            # every read came off the same clock
    obs.close()


# ---------------------------------------------------------------------------
# drift monitoring
# ---------------------------------------------------------------------------
def _monitor(num_features, **kwargs):
    import jax

    from repro.core import ExponentialDotProductKernel

    return DriftMonitor.for_estimator(
        ExponentialDotProductKernel(), 8, num_features,
        estimator="rm", seed=0, **kwargs)


def test_drift_silent_at_bound_satisfying_budget():
    """At a healthy D the observed sup error sits inside eps(D, delta)."""
    mon = _monitor(2048, n_sentinels=8)
    report = mon.check()
    assert report.ok, (report.sup_err, report.eps_bound)
    assert mon.checks == 1 and mon.violations == 0


def test_drift_fires_on_under_budget_features():
    """A drifted/under-provisioned map must trip the monitor: judge a
    small-D map against the (tight) envelope a healthy budget would owe.
    ``margin`` scales the bound the deployment claims to meet."""
    mon = _monitor(8, n_sentinels=8, margin=0.01)
    report = mon.check()
    assert not report.ok
    assert mon.violations == 1
    assert report.sup_err > 0.01 * report.eps_bound


def test_drift_bound_shrinks_with_budget():
    e_small = _monitor(64).eps_bound()
    e_big = _monitor(4096).eps_bound()
    assert e_big < e_small
    # hoeffding core scales as 1/sqrt(D)
    h_small = hoeffding_eps(_monitor(64).kernel, 0.9, 8, 64, 10, 0.05)
    h_big = hoeffding_eps(_monitor(64).kernel, 0.9, 8, 256, 10, 0.05)
    assert h_small / h_big == pytest.approx(2.0)


def test_drift_ingest_keeps_reservoir_in_ball():
    mon = _monitor(256, n_sentinels=8)
    mon.ingest(np.full((32, 8), 10.0))  # way outside the ball
    norms = np.linalg.norm(mon._sentinels, axis=1)
    assert np.all(norms <= mon.radius + 1e-5)
    assert mon._sentinels.shape == (8, 8)


def test_obs_tick_drift_emits_metrics_and_violation_event():
    mon = _monitor(8, n_sentinels=8, margin=0.01)
    obs = Obs(clock=clock.FakeClock(), provenance=PROV,
              drift=mon, drift_every=2)
    obs.tick_drift()                      # tick 1: no check yet
    assert mon.checks == 0
    obs.tick_drift()                      # tick 2: check runs, violates
    assert mon.checks == 1 and mon.violations == 1
    snap = obs.metrics.snapshot(provenance=PROV)
    assert snap["counters"]["drift/violations"] == 1.0
    assert snap["gauges"]["drift/sup_err"] > 0
    assert obs.tracer.events("drift/violation")
    assert obs.tracer.spans("drift/check")
    obs.close()


# ---------------------------------------------------------------------------
# provenance stamps
# ---------------------------------------------------------------------------
def test_platform_provenance_shape():
    from repro.common.env import platform_provenance

    prov = platform_provenance()
    for key in ("backend", "device_kind", "device_count", "interpret",
                "jax_version"):
        assert key in prov
    assert isinstance(prov["interpret"], bool)


def test_default_snapshots_are_provenance_stamped():
    reg = MetricsRegistry(now=clock.FakeClock())
    assert "backend" in reg.snapshot()["provenance"]
    tr = Tracer(now=clock.FakeClock())
    assert "backend" in tr.records[0]["provenance"]


# ---------------------------------------------------------------------------
# CLI + trace checker
# ---------------------------------------------------------------------------
def _write_serve_like_trace(path):
    tr = Tracer(path=path, now=clock.FakeClock(), provenance=PROV)
    tr.event("request/submit", request_id=0)
    tr.event("request/admit", request_id=0, slot=0, bucket=32)
    with tr.span("prefill", bucket=32):
        pass
    with tr.span("decode/step", active=1):
        pass
    tr.event("request/finish", request_id=0, tokens=4)
    tr.close()
    return tr


def test_check_trace_accepts_valid_and_rejects_broken(tmp_path):
    import sys

    sys.path.insert(0, "tools")
    try:
        from check_trace import check_trace
    finally:
        sys.path.pop(0)

    good = tmp_path / "good.jsonl"
    _write_serve_like_trace(good)
    assert check_trace(good) == []

    # missing lifecycle records
    bad = tmp_path / "bad.jsonl"
    tr = Tracer(path=bad, now=clock.FakeClock(), provenance=PROV)
    tr.event("request/submit", request_id=0)
    tr.close()
    errs = check_trace(bad)
    assert any("prefill" in e for e in errs)
    assert any("request/finish" in e for e in errs)

    # meta header missing
    headless = tmp_path / "headless.jsonl"
    headless.write_text('{"type": "event", "name": "x", "ts_us": 0.0}\n')
    assert any("meta" in e for e in check_trace(headless))


def test_obs_cli_summarize_and_chrome(tmp_path, capsys):
    from repro.obs.__main__ import main

    path = tmp_path / "t.jsonl"
    _write_serve_like_trace(path)
    assert main(["summarize", str(path)]) == 0
    out = capsys.readouterr().out
    assert "decode/step" in out and "prefill" in out

    chrome_out = tmp_path / "t.chrome.json"
    assert main(["chrome", str(path), "-o", str(chrome_out)]) == 0
    data = json.loads(chrome_out.read_text())
    assert any(e["ph"] == "X" for e in data["traceEvents"])


def test_bench_check_warns_on_interpret_cpu_artifact(tmp_path, capsys):
    from repro.bench.__main__ import _warn_if_interpret_cpu

    path = tmp_path / "BENCH_x.json"
    path.write_text(json.dumps({
        "provenance": {"backend": "cpu", "interpret": True},
    }))
    _warn_if_interpret_cpu(str(path))
    assert "INTERPRET" in capsys.readouterr().out

    path.write_text(json.dumps({
        "provenance": {"backend": "tpu", "interpret": False},
    }))
    _warn_if_interpret_cpu(str(path))
    assert capsys.readouterr().out == ""
