"""repro.obs under concurrent serving: interleaved multi-request traces
must validate through ``tools/check_trace`` IN-PROCESS (not just the CI
smoke job), including the scheduler's admission/eviction spans — and the
lifecycle checker itself must actually reject malformed interleavings.
"""
import dataclasses
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_model
from repro.obs import Obs, clock
from repro.serve import Request, Scheduler

sys.path.insert(0, "tools")
from check_trace import (  # noqa: E402
    check_records,
    check_request_lifecycles,
)

PROV = {"backend": "test", "device_kind": "test", "device_count": 1,
        "interpret": False, "jax_version": "0"}
VOCAB = 512


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3-1.7b", smoke=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    return cfg, init_model(cfg, jax.random.PRNGKey(0))


def _interleaved_run(cfg, params, *, evict=False):
    """More requests than slots, staggered submits, optional preemption:
    admissions, decodes and finishes interleave across requests."""
    obs = Obs(clock=clock.FakeClock(), provenance=PROV)
    sched = Scheduler(cfg, params, num_slots=2, max_len=32, rng_seed=0,
                      obs=obs)
    rng = np.random.default_rng(0)
    for i in range(3):
        sched.submit(Request(request_id=i,
                             prompt=rng.integers(0, VOCAB, size=4 + i),
                             max_new_tokens=4))
    sched.step()
    # late arrival lands while slots are mid-decode
    sched.submit(Request(request_id=3,
                         prompt=rng.integers(0, VOCAB, size=6),
                         max_new_tokens=3))
    if evict:
        occupied = [i for i, s in enumerate(sched.slots) if s is not None]
        sched.evict(occupied[0], reason="preempted")
    sched.run()
    obs.close()
    return obs


def test_interleaved_trace_validates_in_process(setup):
    """The live Tracer.records of an interleaved 4-request/2-slot run pass
    the full check_records gate — spans, events, lifecycles, Chrome
    conversion — without a file round-trip."""
    obs = _interleaved_run(*setup)
    errors = check_records(obs.tracer.records)
    assert errors == [], errors
    # the run genuinely interleaved: an admit lands after the first finish
    names = [r["name"] for r in obs.tracer.records
             if r["type"] == "event" and r["name"].startswith("request/")]
    first_finish = names.index("request/finish")
    assert "request/admit" in names[first_finish:]


def test_admission_spans_carry_slot_and_bucket(setup):
    obs = _interleaved_run(*setup)
    admits = obs.tracer.spans("admit")
    assert len(admits) == 4
    for sp in admits:
        assert sp["attrs"]["slot"] in (0, 1)
        assert sp["attrs"]["bucket"] == 32
        assert sp["attrs"]["attempt"] >= 1
        assert sp["dur_us"] > 0
    # queue-age gauge was maintained while requests waited
    snap = obs.metrics.snapshot(provenance=PROV)
    assert "serve/queue_age_s" in snap["gauges"]


def test_eviction_spans_validate_and_carry_reason(setup):
    obs = _interleaved_run(*setup, evict=True)
    errors = check_records(obs.tracer.records)
    assert errors == [], errors
    evs = obs.tracer.spans("evict")
    assert len(evs) == 1
    assert evs[0]["attrs"]["reason"] == "preempted"
    discards = obs.tracer.events("request/evict")
    assert len(discards) == 1
    assert discards[0]["attrs"]["tokens_discarded"] >= 1
    # the evicted request was re-admitted: 5 admits for 4 requests
    assert len(obs.tracer.spans("admit")) == 5


# -- the checker must catch malformed interleavings ---------------------------
def _ev(name, **attrs):
    return {"type": "event", "name": name, "ts_us": 0.0, "attrs": attrs}


def test_checker_flags_slot_double_assignment():
    records = [
        _ev("request/submit", request_id=0),
        _ev("request/submit", request_id=1),
        _ev("request/admit", request_id=0, slot=0),
        _ev("request/admit", request_id=1, slot=0),   # 0 still running!
    ]
    errors = check_request_lifecycles(records)
    assert any("double-assignment" in e for e in errors), errors


def test_checker_flags_admit_without_submit_and_after_finish():
    records = [
        _ev("request/admit", request_id=0, slot=0),   # never submitted
        _ev("request/submit", request_id=1),
        _ev("request/admit", request_id=1, slot=1),
        _ev("request/finish", request_id=1, slot=1, tokens=1, reason="eos"),
        _ev("request/admit", request_id=1, slot=1),   # admit after finish
    ]
    errors = check_request_lifecycles(records)
    assert any("never submitted" in e for e in errors), errors
    assert any("'done'" in e for e in errors), errors


def test_checker_flags_duplicate_submit_and_orphan_evict():
    records = [
        _ev("request/submit", request_id=0),
        _ev("request/submit", request_id=0),          # duplicate
        _ev("request/evict", request_id=0, slot=0),   # evict while queued
    ]
    errors = check_request_lifecycles(records)
    assert any("duplicate submit" in e for e in errors), errors
    assert any("evict while" in e for e in errors), errors


def test_checker_accepts_evict_readmit_cycle():
    records = [
        _ev("request/submit", request_id=0),
        _ev("request/admit", request_id=0, slot=0),
        _ev("request/evict", request_id=0, slot=0),
        _ev("request/admit", request_id=0, slot=1),
        _ev("request/finish", request_id=0, slot=1, tokens=2,
            reason="max_new_tokens"),
    ]
    assert check_request_lifecycles(records) == []


def test_checker_accepts_truncated_inflight_requests():
    """Requests still queued or running at trace end are legal."""
    records = [
        _ev("request/submit", request_id=0),
        _ev("request/submit", request_id=1),
        _ev("request/admit", request_id=0, slot=0),
    ]
    assert check_request_lifecycles(records) == []


def test_step_span_tree_on_fake_clock(setup):
    """Each decode step with k busy lanes holds one ``sample`` span
    (``lanes == k``) and one ``fetch`` span inside ``decode/step`` inside
    ``step``; each admission's first token has its own pair in its tick
    outside any decode step; ``serve/host_syncs`` counts the fetches, one
    per busy decode step and one per admission, and
    ``serve/sampled_lanes`` the lanes they served, one per emitted
    token."""
    cfg, params = setup
    obs = Obs(clock=clock.FakeClock(), provenance=PROV)
    sched = Scheduler(cfg, params, num_slots=2, max_len=32, rng_seed=0,
                      obs=obs)
    rng = np.random.default_rng(1)
    for i, n_new in enumerate((3, 1, 4, 2)):
        sched.submit(Request(request_id=i,
                             prompt=rng.integers(0, VOCAB, size=4 + i),
                             max_new_tokens=n_new))
    infos = []
    while sched.pending():
        infos.append(sched.step())
    obs.close()

    def spans(name):
        return sorted((sp["ts_us"], sp["ts_us"] + sp["dur_us"],
                       sp["attrs"].get("lanes"))
                      for sp in obs.tracer.spans(name))

    def inside(inner, a, b):
        return [s for s in inner if a < s[0] and s[1] < b]

    ticks, decodes = spans("step"), spans("decode/step")
    samples, fetches = spans("sample"), spans("fetch")
    assert len(ticks) == len(infos)
    busy = [i for i in infos if i.active]
    assert len(decodes) == len(busy) > 0
    assert any(i.active >= 2 for i in busy)
    for (a, b, _), info in zip(decodes, busy):
        assert any(x < a and b < y for x, y, _ in ticks)
        (sample,) = inside(samples, a, b)
        assert sample[2] == info.active
        assert len(inside(fetches, a, b)) == 1
    admitted = sum(len(i.admitted) for i in infos)
    outside = [s for s in samples + fetches
               if not any(a < s[0] and s[1] < b for a, b, _ in decodes)]
    assert len(outside) == 2 * admitted
    assert all(s[2] == 1 for s in outside if s in samples)
    for s0, s1, _ in samples + fetches:
        assert any(a < s0 and s1 < b for a, b, _ in ticks)
    # a sample is followed by its fetch, before anything else is sampled
    order = sorted([(a, "sample") for a, _, _ in samples]
                   + [(a, "fetch") for a, _, _ in fetches])
    assert [k for _, k in order] == ["sample", "fetch"] * len(samples)
    assert len(fetches) == len(busy) + admitted
    snap = obs.metrics.snapshot(provenance=PROV)
    assert snap["counters"]["serve/host_syncs"] == len(busy) + admitted
    tokens = sum(i.new_tokens for i in infos)
    assert snap["counters"]["serve/sampled_lanes"] == tokens
