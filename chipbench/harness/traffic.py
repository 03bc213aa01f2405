"""One general traffic generator, driven by a traffic file.

A traffic file (``chipbench/traffic/<name>.json``) holds parameters only:

    {"kind": "batch" | "open",
     "block": 32,                       # requests per stratified block
     "prompt": {"dist": "exponential", "mean": 161.31, "min": 4, "max": 640},
     "output": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                "min": 8, "max": 64},
     "queue_min": 8,                    # batch: queued requests kept waiting
     "rate": 2.5, "lead_s": 6.0}        # open: Poisson rate, lead-in

Lengths are exponential (``mean``) or lognormal (``median``, ``sigma``;
the default), clipped to [min, max]. So that the
seed changes the order of the work and not its amount, every block of
``block`` consecutive requests holds the same multiset of prompt lengths,
output lengths and (open loop) inter-arrival gaps: the lengths at the
quantiles ``(i + 0.5) / block`` of the clipped distribution, and the gaps at
the same quantiles of Exp(rate). The seed permutes each block's prompt
lengths, output lengths and gaps independently, and draws the prompt tokens.

``Arrival`` and ``prompt_for`` are copied from ``repro.bench.loadgen`` (its
``Arrival`` record and ``_prompt_for``), so that the benchmark does not move
when the program's load generator does.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

KINDS = ("batch", "open")


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One scheduled request: when it is due and what it asks for."""

    t: float                 # due time, seconds after the traffic starts
    request_id: int
    prompt_len: int
    max_new_tokens: int


def seed_words(seed: int, n: int = 4) -> np.ndarray:
    """``n`` uint32 words from any non-negative whole-number seed."""
    return np.random.SeedSequence(int(seed)).generate_state(n)


def _quantile_lengths(spec: Dict, block: int) -> np.ndarray:
    """Lengths at the block's quantiles, clipped to [min, max]."""
    qs = [(i + 0.5) / block for i in range(block)]
    if spec.get("dist", "lognormal") == "exponential":
        out = [-float(spec["mean"]) * math.log(1.0 - q) for q in qs]
    else:
        mu, sigma, nd = (math.log(float(spec["median"])),
                         float(spec["sigma"]), NormalDist())
        out = [math.exp(mu + sigma * nd.inv_cdf(q)) for q in qs]
    return np.clip(np.rint(out), spec["min"], spec["max"]).astype(np.int64)


def _quantile_gaps(rate: float, block: int) -> np.ndarray:
    """Exp(rate) inter-arrival gaps at the block's quantiles."""
    return np.asarray([-math.log(1.0 - (i + 0.5) / block) / rate
                       for i in range(block)])


def validate(spec: Dict) -> None:
    if spec.get("kind") not in KINDS:
        raise ValueError(f"traffic kind must be one of {KINDS}, got "
                         f"{spec.get('kind')!r}")
    for part in ("prompt", "output"):
        d = spec[part]
        dist = d.get("dist", "lognormal")
        centre = {"exponential": "mean", "lognormal": "median"}.get(dist)
        if centre is None:
            raise ValueError(f"traffic {part}: unknown dist {dist!r}")
        if not 1 <= d["min"] <= d["max"] or not d[centre] > 0:
            raise ValueError(f"traffic {part}: need 1 <= min <= max and a "
                             f"positive {centre}, got {d}")
    if spec["kind"] == "open" and float(spec["rate"]) <= 0:
        raise ValueError("open traffic needs a positive rate")


def longest(spec: Dict) -> int:
    """Most positions one request of this traffic can occupy."""
    return int(spec["prompt"]["max"]) + int(spec["output"]["max"])


def arrivals(spec: Dict, seed: int, count: int) -> List[Arrival]:
    """The first ``count`` requests of this traffic under ``seed``.

    Batch traffic has every request due at 0; open traffic is due on the
    Poisson schedule, the first request one gap after 0.
    """
    validate(spec)
    block = int(spec["block"])
    rng = np.random.Generator(np.random.Philox(seed_words(seed)))
    plens = _quantile_lengths(spec["prompt"], block)
    olens = _quantile_lengths(spec["output"], block)
    gaps = (_quantile_gaps(float(spec["rate"]), block)
            if spec["kind"] == "open" else np.zeros(block))
    out: List[Arrival] = []
    t = 0.0
    while len(out) < count:
        p, o, g = (rng.permutation(plens), rng.permutation(olens),
                   rng.permutation(gaps))
        for i in range(block):
            if len(out) == count:
                break
            t += float(g[i])
            out.append(Arrival(t=t, request_id=len(out),
                               prompt_len=int(p[i]),
                               max_new_tokens=int(o[i])))
    return out


def prompt_for(arrival: Arrival, vocab_size: int, seed: int) -> np.ndarray:
    """The prompt tokens of one request: uniform over the vocabulary."""
    rng = np.random.default_rng((*seed_words(seed, 2), arrival.request_id))
    return rng.integers(0, vocab_size, size=arrival.prompt_len,
                        dtype=np.int64).astype(np.int32)
