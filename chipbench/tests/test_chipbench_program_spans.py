"""The program's own spans in a profiler trace: read back from a profile of
the scheduler on the CPU, and the device's idle time put down to them, on a
made-up trace with known answers (``tests/data/program_trace.json``) and on
the small recorded trace, which holds none. CPU only."""
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import programspans, tracefile  # noqa: E402

DATA = BENCH / "tests" / "data"


@pytest.fixture(scope="module")
def made_up():
    return json.loads((DATA / "program_trace.json").read_text())


def test_innermost_is_the_span_that_started_last():
    spans = [["repro.step", 0, 10], ["repro.decode/step", 0, 6],
             ["repro.sample", 2, 3], ["repro.fetch", 3, 5],
             ["repro.step", 12, 14]]
    assert programspans.innermost(spans) == [
        (0, 2, "repro.decode/step"), (2, 3, "repro.sample"),
        (3, 5, "repro.fetch"), (5, 6, "repro.decode/step"),
        (6, 10, "repro.step"), (12, 14, "repro.step")]
    assert programspans.innermost([]) == []


def test_made_up_idle_by_program_span(made_up):
    tr = programspans.reduce(made_up)
    assert len(tr.program) == 12          # the tick before the window left
    idle = tr.idle_by_program_span()
    want_ms = {"outside": 4, "repro.step": 8, "repro.admit": 2,
               "repro.prefill": 6, "repro.sample": 11, "repro.fetch": 11,
               "repro.decode/step": 8}
    assert idle == {k: pytest.approx(v * 1e-3) for k, v in want_ms.items()}
    assert sum(idle.values()) == pytest.approx(tr.window_s - tr.busy_s)


def test_made_up_per_step(made_up):
    tr = programspans.reduce(made_up)
    # decode steps starting at 38 and 98 ms; fetches at 35, 66 and 80 ms
    assert tr.per_step() == {
        "steps": 2,
        "sampling_idle_ms_per_step": pytest.approx(22 / 2),
        "scheduler_idle_ms_per_step": pytest.approx(24 / 2),
        "host_syncs_per_step": pytest.approx(3 / 2),
    }


def test_made_up_leaves_the_harness_reduction_alone(made_up):
    """What ``tracefile`` reads from the same records is unchanged."""
    old = tracefile.reduce(made_up)
    new = programspans.reduce(made_up)
    assert new.breakdown() == old.breakdown()
    assert new.idle_by_activity() == old.idle_by_activity()
    assert (new.busy_s, new.window_s) == (old.busy_s, old.window_s)
    assert new.module_time("_decode_compiled") == \
        old.module_time("_decode_compiled")


def test_recorded_trace_holds_no_program_spans():
    """A profile of a program that opens no ``repro.*`` span: every idle
    instant is outside, and there is nothing per step."""
    rec = json.loads((DATA / "small_trace.json").read_text())
    tr = programspans.reduce(rec)
    assert tr.program == []
    idle = tr.idle_by_program_span()
    assert list(idle) == [programspans.OUTSIDE]
    assert idle[programspans.OUTSIDE] == pytest.approx(
        tr.window_s - tr.busy_s, rel=1e-9)
    assert tr.per_step() is None
    assert tr.breakdown() == tracefile.reduce(rec).breakdown()


def test_scheduler_spans_read_back_from_a_profile(tmp_path):
    """A tiny scheduler with observability off, under the profiler on the
    CPU: the profile's ``repro.*`` spans nest as the scheduler ran them,
    one ``sample`` and one ``fetch`` per busy lane in each decode step."""
    import jax

    from repro.configs import get_config
    from repro.models import init_model
    from repro.serve import Request, Scheduler

    cfg = dataclasses.replace(get_config("qwen3-1.7b", smoke=True),
                              compute_dtype="float32")
    sched = Scheduler(cfg, init_model(cfg, jax.random.PRNGKey(0)),
                      num_slots=2, max_len=32, rng_seed=0)
    rng = np.random.default_rng(0)
    for i, n_new in enumerate((3, 2, 4)):
        sched.submit(Request(request_id=i,
                             prompt=rng.integers(0, cfg.vocab_size, size=5),
                             max_new_tokens=n_new))
    sched.step()                                  # compile outside the trace
    infos = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            while sched.pending():
                infos.append(sched.step())
    finally:
        jax.profiler.stop_trace()
    (path,) = tracefile.find(tmp_path)
    tr = programspans.reduce(programspans.load(path))

    def named(name):
        return sorted((a, b) for n, a, b in tr.program if n == name)

    def inside(spans, a, b):
        return [(x, y) for x, y in spans if a <= x and y <= b]

    ticks, decodes = named("repro.step"), named("repro.decode/step")
    samples, fetches = named("repro.sample"), named("repro.fetch")
    assert len(ticks) == len(infos)
    assert len(decodes) == sum(1 for i in infos if i.active)
    for (a, b), info in zip(decodes, [i for i in infos if i.active]):
        assert any(x <= a and b <= y for x, y in ticks)
        assert len(inside(samples, a, b)) == info.active
        assert len(inside(fetches, a, b)) == info.active
    # a first token is sampled at admission, outside any decode step
    assert len(samples) == len(fetches) == sum(i.new_tokens for i in infos)
    for a, b in samples + fetches:
        assert any(x <= a and b <= y for x, y in ticks)
