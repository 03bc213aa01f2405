"""The check that decides ``correct``: served greedy tokens against the
plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests the window served is run through the configuration's
reference once each, teacher-forced over its prompt and served tokens, in
float32 at "highest" matmul precision. The sample holds the request with
the most served tokens and one request of every decode lane, drawn from
the seed, so that a fault in some lanes only cannot slip past it. At
each served position the gap ``max(ref) - ref[served]`` says how far below
the reference's best the served token's logit lies; greedy tokens of a
faithful program lie within rounding of the best. The number compared is
the widest gap, ``max_logit_gap``.

The control (``control=True``, not run by the benchmark's own runs) puts
the reference in the program's place at the next precision below the
configuration's bf16 compute: every matmul operand rounded to fp8 (e4m3:
3 mantissa bits, largest magnitude 448) with one scale per tensor. The
rounding is done in float32 arithmetic, so it needs no fp8 type on the
device. At each position of the same sequences it takes the token the
fp8 logits put first and reads that token's gap in the float32
reference, ``control_logit_gap``. In a control run that number, and not
the program's, decides ``correct``: the control stands in the program's
place and has to come out not correct.
"""
from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

from harness import traffic as traffic_mod

FP8_MAX = 448.0            # largest finite e4m3 magnitude
FP8_MIN_EXP = -6           # smallest normal exponent; below it, subnormal
FP8_MANTISSA = 3
MIN_TOKENS = 300           # served tokens compared at the least, if served


def log(msg: str) -> None:
    print(f"[check] {msg}", file=sys.stderr, flush=True)


def fp8_round(x):
    """Round to fp8 e4m3 with one scale per tensor (the largest magnitude
    goes to 448), nearest-even, back to float32."""
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    y = x / scale
    a = jnp.abs(y)
    e = jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** FP8_MIN_EXP)))
    step = 2.0 ** (e - FP8_MANTISSA)
    q = jnp.minimum(jnp.round(a / step) * step, FP8_MAX)
    return jnp.sign(y) * q * scale


def sample(requests: List[Dict], t_close: float, seed: int) -> List[Dict]:
    """Requests to compare: the one with the most served tokens, then one
    of each decode lane, drawn from the seed among the lane's requests that
    finished by ``t_close``, or, where the lane finished none, the request
    it held at the close with the tokens it had served; then others in the
    seed's order until they hold ``MIN_TOKENS`` served tokens."""
    served = [r for r in requests if r["generated"] and r["lane"] is not None]
    if not served:
        return []
    order = np.random.Generator(np.random.Philox(
        traffic_mod.seed_words(seed, 4) ^ np.uint32(0x5EED))).permutation(
            len(served))
    picked = [max(served, key=lambda r: len(r["generated"]))]
    for lane in sorted({r["lane"] for r in served}):
        mine = [served[int(i)] for i in order
                if served[int(i)]["lane"] == lane]
        done = [r for r in mine
                if r["done"] is not None and r["done"] <= t_close]
        picked.append((done or mine)[0])
    out = list({r["rid"]: r for r in picked}.values())
    tokens = sum(len(r["generated"]) for r in out)
    for i in order:
        r = served[int(i)]
        if tokens >= MIN_TOKENS:
            break
        if all(r["rid"] != o["rid"] for o in out):
            out.append(r)
            tokens += len(r["generated"])
    return out


def _gap_fn(jax, ref, config, max_len: int, n_out: int, control: bool):
    """Jitted: tokens [max_len], idx [n_out], served [n_out] -> gaps."""
    import jax.numpy as jnp

    def fn(weights, tokens, idx, served):
        with jax.default_matmul_precision("highest"):
            hid = ref.hidden(config, weights, tokens)
            logits = ref.logits_at(weights, hid, idx)
            best = jnp.max(logits, -1)
            rows = jnp.arange(n_out)
            gap = best - logits[rows, served]
            if not control:
                return gap, gap
            hid_c = ref.hidden(config, weights, tokens, fp8_round)
            first = jnp.argmax(ref.logits_at(weights, hid_c, idx, fp8_round),
                               -1)
            return gap, best - logits[rows, first]

    return jax.jit(fn)


def run_check(jax, ref, config: Dict, weights, run, seed: int, *,
              control: bool = False) -> Dict:
    chk = config["check"]
    max_len = int(config["serving"]["max_len"])
    n_out = int(run.cell["traffic"]["output"]["max"])
    picked = sample(run.requests, run.t_close, seed)
    limit = float(chk["max_logit_gap"])
    if not picked:
        log("no request served a token: nothing to compare")
        return {"correct": False,
                "compared": {"requests": {"value": 0, "limit": 1}}}
    fn = _gap_fn(jax, ref, config, max_len, n_out, control)
    worst, worst_ctrl, n_tok = 0.0, 0.0, 0
    for r in picked:
        gen = np.asarray(r["generated"], np.int32)
        seq = np.concatenate([np.asarray(r["prompt"], np.int32), gen[:-1]])
        tokens = np.zeros(max_len, np.int32)
        tokens[:len(seq)] = seq
        n = len(gen)
        idx = np.full(n_out, r["prompt_len"] - 1 + n - 1, np.int32)
        idx[:n] = r["prompt_len"] - 1 + np.arange(n)
        served = np.full(n_out, gen[-1], np.int32)
        served[:n] = gen
        gap, gap_c = (np.asarray(x)[:n] for x in fn(weights, tokens, idx,
                                                   served))
        worst = max(worst, float(gap.max()))
        worst_ctrl = max(worst_ctrl, float(gap_c.max()))
        n_tok += n
    log(f"compared {len(picked)} requests over "
        f"{len({r['lane'] for r in picked})} lanes, {n_tok} served tokens")
    compared = {"max_logit_gap": {"value": worst, "limit": limit},
                "requests": {"value": len(picked), "limit": 1},
                "served_tokens": {"value": n_tok, "limit": 1}}
    ok = worst <= limit
    if control:
        compared["control_logit_gap"] = {"value": worst_ctrl,
                                         "limit": limit}
        ok = worst_ctrl <= limit
    return {"correct": ok, "compared": compared}
