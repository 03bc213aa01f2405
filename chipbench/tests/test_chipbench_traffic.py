"""The traffic generator's determinism and the window accounting, on a
made-up clock. CPU only, no program code."""
import json
import math
import sys
from collections import Counter
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import stats, traffic  # noqa: E402

SPECS = sorted((BENCH / "traffic").glob("*.json"))
BIG_SEED = 2 ** 31 + 12345


def _spec(name):
    return json.loads((BENCH / "traffic" / name).read_text())


@pytest.mark.parametrize("path", SPECS, ids=lambda p: p.stem)
def test_same_seed_same_requests(path):
    spec = json.loads(path.read_text())
    a = traffic.arrivals(spec, BIG_SEED, 100)
    b = traffic.arrivals(spec, BIG_SEED, 100)
    assert a == b
    assert a != traffic.arrivals(spec, BIG_SEED + 1, 100)
    p1 = traffic.prompt_for(a[3], 151936, BIG_SEED)
    p2 = traffic.prompt_for(a[3], 151936, BIG_SEED)
    assert (p1 == p2).all() and len(p1) == a[3].prompt_len
    assert p1.max() < 151936 and p1.min() >= 0


@pytest.mark.parametrize("path", SPECS, ids=lambda p: p.stem)
def test_every_block_holds_the_same_work(path):
    """The seed reorders the work of each block; it does not change it."""
    spec = json.loads(path.read_text())
    block = spec["block"]
    runs = [traffic.arrivals(spec, s, 3 * block) for s in (1, 7, BIG_SEED)]
    ref = None
    for reqs in runs:
        for k in range(3):
            blk = reqs[k * block:(k + 1) * block]
            work = (Counter(r.prompt_len for r in blk),
                    Counter(r.max_new_tokens for r in blk))
            assert ref is None or work == ref
            ref = work
            for r in blk:
                assert spec["prompt"]["min"] <= r.prompt_len \
                    <= spec["prompt"]["max"]
                assert spec["output"]["min"] <= r.max_new_tokens \
                    <= spec["output"]["max"]
    if spec["kind"] == "open":
        ends = {round(reqs[block - 1].t, 9) for reqs in runs}
        assert len(ends) == 1, "a block's arrivals must span one duration"
        # mean gap of a block is 1 / rate to within the quantile grid
        assert abs(runs[0][block - 1].t / block - 1 / spec["rate"]) \
            < 0.1 / spec["rate"]
    else:
        assert all(r.t == 0.0 for r in runs[0])


def test_lengths_follow_the_stated_median():
    """A block's middle lengths bracket the stated distribution's median:
    ``median`` for a lognormal, ``mean * ln 2`` for an exponential, whose
    block mean is the stated mean to within the quantile grid and clip."""
    for name in ("decode-batch.json", "prefill-open.rm.json"):
        spec = _spec(name)
        reqs = traffic.arrivals(spec, 0, spec["block"])
        for part, lens in (("prompt", [r.prompt_len for r in reqs]),
                           ("output", [r.max_new_tokens for r in reqs])):
            d, lens = spec[part], sorted(lens)
            exp = d.get("dist") == "exponential"
            median = d["mean"] * math.log(2) if exp else d["median"]
            mid = len(lens) // 2
            assert lens[mid - 1] <= median <= lens[mid], (name, part)
            if exp:
                assert abs(sum(lens) / len(lens) / d["mean"] - 1) < 0.02


def test_percentile_is_nearest_rank():
    vals = [float(i) for i in range(1, 101)]
    assert stats.percentile(vals, 90) == 90.0
    assert stats.percentile(vals, 95) == 95.0
    assert stats.percentile([3.0], 90) == 3.0
    assert stats.percentile([], 90) is None
    assert stats.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.0


def test_ttft_counts_from_due_time_and_censors():
    """A fake clock: the window is [10, 20)."""
    reqs = [
        {"due": 9.0, "first": 12.0},      # due before the window: left out
        {"due": 10.0, "first": 10.5},     # 0.5 from due, not from submit
        {"due": 15.0, "first": 19.0},     # 4.0
        {"due": 18.0, "first": None},     # no first token: age 2.0 at close
        {"due": 19.5, "first": 25.0},     # first token after close: 0.5
        {"due": 20.0, "first": 20.1},     # due at close: left out
    ]
    assert sorted(stats.ttft_due(reqs, 10.0, 20.0)) == [0.5, 0.5, 2.0, 4.0]


def test_itl_and_tokens_use_the_later_token():
    times = [[9.0, 9.5, 10.5, 11.0], [19.0, 20.5], [21.0, 22.0]]
    gaps = stats.inter_token_gaps(times, 10.0, 20.0)
    assert sorted(gaps) == [0.5, 1.0]          # 9.5 -> 10.5 and 10.5 -> 11.0
    assert stats.tokens_in_window(times, 10.0, 20.0) == 3
    assert stats.rate(3, 10.0, 20.0) == 0.3


def test_validate_refuses_bad_traffic():
    spec = _spec("decode-batch.json")
    with pytest.raises(ValueError):
        traffic.validate(dict(spec, kind="closed"))
    with pytest.raises(ValueError):
        traffic.validate(dict(spec, prompt=dict(spec["prompt"], min=700)))
    with pytest.raises(ValueError):
        traffic.validate(dict(spec, output=dict(spec["output"], mean=0)))
    with pytest.raises(ValueError):
        traffic.validate(dict(spec, output=dict(spec["output"], dist="zipf")))
