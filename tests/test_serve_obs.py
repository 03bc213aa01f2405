"""Serving observability: the instrumented engine must (a) emit the exact
request lifecycle on a deterministic clock, and (b) be bit-identical to the
uninstrumented engine — observability can never touch a decoded token."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_model
from repro.obs import Obs, clock
from repro.serve import Request, ServingEngine
from repro.serve.engine import _bucket

PROV = {"backend": "test", "device_kind": "test", "device_count": 1,
        "interpret": False, "jax_version": "0"}


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3-1.7b", smoke=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    params = init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _run_two_requests(cfg, params, obs):
    rng = np.random.default_rng(0)
    engine = ServingEngine(cfg, params, num_slots=1, max_len=64, obs=obs)
    for i in range(2):
        engine.submit(Request(request_id=i,
                              prompt=rng.integers(0, cfg.vocab_size, size=5),
                              max_new_tokens=2))
    return engine, engine.run()


def test_lifecycle_event_sequence_on_fake_clock(setup):
    """One slot, two requests, two tokens each: the trace must show the
    full scripted lifecycle — submit x2, then admit -> prefill ->
    finish-inside-decode per request (spans are recorded at close, so the
    decode/step span lands after the finish event it contains)."""
    cfg, params = setup
    obs = Obs(clock=clock.FakeClock(), provenance=PROV)
    engine, done = _run_two_requests(cfg, params, obs)

    names = [r["name"] for r in obs.tracer.records if r["type"] != "meta"]
    assert names == [
        "request/submit", "request/submit",
        "request/admit", "prefill", "request/finish", "decode/step",
        "request/admit", "prefill", "request/finish", "decode/step",
    ]

    submits = obs.tracer.events("request/submit")
    assert [e["attrs"]["request_id"] for e in submits] == [0, 1]
    assert all(e["attrs"]["prompt_len"] == 5 for e in submits)
    admits = obs.tracer.events("request/admit")
    assert [e["attrs"]["slot"] for e in admits] == [0, 0]
    assert all(e["attrs"]["bucket"] == 32 for e in admits)
    finishes = obs.tracer.events("request/finish")
    assert [e["attrs"]["tokens"] for e in finishes] == [2, 2]
    assert all(e["attrs"]["reason"] == "max_new_tokens" for e in finishes)
    for sp in obs.tracer.spans("prefill"):
        assert sp["attrs"]["bucket"] == 32 and sp["attrs"]["prompt_len"] == 5
        assert sp["dur_us"] > 0
    obs.close()


def test_lifecycle_histograms_hold_exact_fake_clock_values(setup):
    """Histogram VALUES (not just counts) are pinned by the fake clock:
    every duration is a difference of deterministic clock reads, so the
    recorded TTFTs equal the engine's own timestamp fields exactly."""
    cfg, params = setup
    obs = Obs(clock=clock.FakeClock(step=1.0), provenance=PROV)
    engine, done = _run_two_requests(cfg, params, obs)

    ttft = obs.metrics.histogram("serve/ttft_s")
    expect = sorted(s.t_first_token - s.t_enqueue for s in done.values())
    assert sorted(ttft._vals) == expect
    assert ttft.count == 2
    # every fake-clock duration is a whole number of 1.0s steps and spans
    # real work: submit->first-token crosses the prefill span (>= 2 reads)
    assert all(v == int(v) and v >= 2.0 for v in ttft._vals)

    lat = obs.metrics.histogram("serve/token_latency_s")
    assert lat.count == 2                      # one decode iteration per req
    assert all(v == int(v) and v > 0 for v in lat._vals)
    tps = obs.metrics.histogram("serve/tokens_per_s")
    assert tps.count == 2
    expect_tps = sorted(2.0 / (s.t_done - s.t_enqueue)
                        for s in done.values())
    assert sorted(tps._vals) == expect_tps

    snap = obs.metrics.snapshot(provenance=PROV)
    assert snap["counters"]["serve/requests_submitted"] == 2.0
    assert snap["counters"]["serve/tokens_generated"] == 2.0
    assert snap["gauges"]["serve/queue_depth"] == 0.0
    assert snap["gauges"]["serve/slots_occupied"] == 0.0
    obs.close()


def test_obs_disabled_is_bit_identical(setup):
    """obs=None and a fully-enabled Obs must produce the same tokens —
    instrumentation never touches a jax value."""
    cfg, params = setup
    _, done_off = _run_two_requests(cfg, params, None)
    obs = Obs(clock=clock.FakeClock(), provenance=PROV)
    _, done_on = _run_two_requests(cfg, params, obs)
    obs.close()
    assert {i: s.generated for i, s in done_off.items()} == \
           {i: s.generated for i, s in done_on.items()}


def test_bucket_raises_clear_valueerror_on_oversized_prompt():
    """Regression: prompts beyond the largest bucket used to fall into an
    unbounded round-up; now they fail fast with the max length named."""
    assert _bucket(2048) == 2048
    with pytest.raises(ValueError, match="2048"):
        _bucket(2049)


def test_submit_rejects_prompt_at_or_beyond_max_len(setup):
    cfg, params = setup
    engine = ServingEngine(cfg, params, num_slots=1, max_len=16)
    with pytest.raises(ValueError, match="max_len 16"):
        engine.submit(Request(request_id=0,
                              prompt=np.zeros(16, np.int64)))
    # one-under still admits fine at the engine API level
    engine.submit(Request(request_id=1, prompt=np.zeros(15, np.int64),
                          max_new_tokens=1))
