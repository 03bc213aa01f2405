"""The reduction from a profiler trace to the per-layer numbers: idle share,
time by program and kernel name, and what the host was doing in each idle
gap. First on a hand-made trace with known answers, then on a small trace
recorded on a v5e (``tests/data/small_trace.json``: an admission prefill
and the two decode steps after it in the rm decode-batch cell, cut from a
full trace), against a plain recount. CPU only."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import tracefile  # noqa: E402

MS = 1_000_000     # ns


def _made_up():
    """Window 0..100 ms. Device busy 10-30 (prefill, a kernel inside),
    40-50 and 45-60 (overlapping ops of a decode), 90-120 (past the end).
    Host: a step 0-100 holding a prefill dispatch 5-35 and a decode
    dispatch 38-62; a wait 70-95."""
    return {
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.step", 0, 100 * MS],
                 ["bench.prefill", 5 * MS, 35 * MS],
                 ["bench.decode", 38 * MS, 62 * MS],
                 ["bench.wait", 70 * MS, 95 * MS]],
        "modules": [["jit__prefill_compiled(1)", 10 * MS, 30 * MS],
                    ["jit__decode_compiled(2)", 40 * MS, 60 * MS],
                    ["jit__decode_compiled(2)", 90 * MS, 120 * MS]],
        "ops": [["fusion", 10 * MS, 20 * MS],
                ["rm_fused_attention_pallas", 20 * MS, 30 * MS],
                ["fusion", 40 * MS, 50 * MS],
                ["convolution", 45 * MS, 60 * MS],
                ["fusion", 90 * MS, 120 * MS]],
        "devices": 1,
    }


def test_op_names_are_short():
    assert tracefile._op_name("%rm_feature_fused_pallas.12 = f32[8] "
                              "custom-call(f32[8] %x)") == \
        "rm_feature_fused_pallas"
    assert tracefile._op_name("%fusion.3 = bf16[2] fusion()") == "fusion"


def test_made_up_trace():
    tr = tracefile.reduce(_made_up())
    assert tr.window_s == pytest.approx(0.1)
    # busy: 10-30, 40-60, 90-100 (clipped) = 50 ms
    assert tr.busy_s == pytest.approx(0.05)
    assert tr.gaps() == [(0, 10 * MS), (30 * MS, 40 * MS),
                         (60 * MS, 90 * MS), ]
    idle = tr.idle_by_activity()
    assert idle["prefill dispatch"] == pytest.approx(0.010)       # 0-10
    assert idle["sampling and scheduler"] == pytest.approx(0.010)  # 30-40
    assert idle["waiting for an arrival"] == pytest.approx(0.030)  # 60-90
    # 40-60 and the part of 90-120 inside the window
    assert tr.module_time("_decode_compiled") == (pytest.approx(0.03), 2)
    assert tr.op_time("rm_fused_attention_pallas") == (pytest.approx(0.01),
                                                       1)
    top = dict(tr.top_ops())
    assert top["fusion"] == pytest.approx(0.03)
    bd = tr.breakdown()
    assert bd["idle_gaps"][0] == ["waiting for an arrival",
                                  pytest.approx(0.03)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_no_window_is_an_error():
    rec = _made_up()
    rec["host"] = rec["host"][1:]
    with pytest.raises(ValueError):
        tracefile.reduce(rec)


@pytest.fixture(scope="module")
def recorded():
    return json.loads((BENCH / "tests" / "data" /
                       "small_trace.json").read_text())


def test_recorded_trace_against_a_recount(recorded):
    tr = tracefile.reduce(recorded)
    lo, hi = tr.lo, tr.hi
    # busy time by a plain sweep over the clipped operation edges
    edges = []
    for _, a, b, _ in recorded["ops"]:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    busy, depth, last = 0, 0, None
    for t, d in sorted(edges):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert tr.busy_s == pytest.approx(busy * 1e-9, rel=1e-9)
    assert 0 < tr.busy_s < tr.window_s
    idle = sum(tr.idle_by_activity().values())
    assert idle == pytest.approx(tr.window_s - tr.busy_s, rel=1e-9)
    # every program and operation in the window is counted once
    for part in ("_decode_compiled", "_prefill_compiled"):
        want = [(max(a, lo), min(b, hi)) for n, a, b in recorded["modules"]
                if part in n and b > lo and a < hi]
        got = tr.module_time(part)
        assert got[1] == len(want) > 0
        assert got[0] == pytest.approx(sum(b - a for a, b in want) * 1e-9)
    for kernel in ("rm_fused_attention_pallas", "rm_feature_fused_pallas"):
        want = [(a, b) for n, a, b, _ in recorded["ops"]
                if kernel in n and b > lo and a < hi]
        assert tr.op_time(kernel)[1] == len(want) > 0


def test_recorded_gaps_are_put_down_to_the_host(recorded):
    tr = tracefile.reduce(recorded)
    idle = tr.idle_by_activity()
    assert set(idle) <= set(tracefile.ACTIVITY.values()) | {tracefile.OUTSIDE}
    for a, b in tr.gaps():
        mid = (a + b) / 2
        spans = [(y - x, n) for n, x, y in recorded["host"]
                 if n != "bench.window" and x <= mid < y]
        want = (tracefile.ACTIVITY[min(spans)[1]] if spans
                else tracefile.OUTSIDE)
        assert tr.activity(mid) == want
