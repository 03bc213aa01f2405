#!/usr/bin/env python3
"""Smoke test of the served path on TPU: the Pallas kernels, then qwen3-1.7b
at its full published width through the continuous-batching Scheduler.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # DP decode + sharded Gram on four chips

One process drives every device. Off the TPU (including under
``JAX_PLATFORMS=cpu``), or without this repository's ``src/`` beside it, the
script exits non-zero before doing any work. Every check raises on failure,
so any failed phase exits non-zero. Lines starting ``[smoke]`` are smoke
output (compile seconds, wall times, tile choices), not benchmark metrics.
On success the last line is ``{"ok": true, "device": {...}}``.

One chip:
  1. kernel parity — the compiled ``rm_feature_fused`` (fp32, bf16) and
     ``rm_attention_fused_prefill`` / ``_decode_step`` against their jnp
     oracles at the served widths (head_dim 128, F=256, n_max 8, bucket
     2048, 4 slots x 16 heads);
  2. serving — qwen3-1.7b FULL, 4 slots, max_len 2048, 8 requests whose
     prompts span three prefill buckets, 32 new tokens each, in
     ``attention_mode="rm"`` and ``"exact"``. In rm mode the compiled
     prefill and decode programs must hold ``tpu_custom_call``, and the
     fused prefill's logits must match the two-launch path's.

Four chips (``--chips 4``), and nothing else:
  1. DP decode — the same 8 requests over a (4, 1) host mesh with 8 slots:
     parameters and cache must be spread over all four devices, and the
     tokens must match one device with 2 slots (the per-device batch).
     Against one device with 8 slots, token matches and logit gaps are
     logged, beside the gap that batch shape alone leaves on one device;
  2. sharded Gram — ``sharded_estimate_gram`` over a 4-shard feature mesh
     against the single-device Gram.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "qwen3-1.7b"
MAX_LEN = 2048
MAX_NEW = 32
# prompt lengths over 16..1000 tokens: prefill buckets 32, 128 and 1024
PROMPT_LENS = (16, 24, 30, 100, 120, 900, 950, 1000)
SEED = 0

# kernel widths of the served rm attention
HEAD_DIM, HEADS, SLOTS, BUCKET = 128, 16, 4, 2048
# tolerances: fp32 parity 1e-5 as in tests/test_rm_*_fused.py (applied
# normwise, see _close_fp32); bf16 feature budget for "rm" as in
# tests/test_precision.py
FP32_TOL = 1e-5
RM_BF16_FEATURE_ATOL = 5e-3
# fused vs two-launch prefill logits under bf16 compute: a few bf16 ulps
# (2^-8 each) of the largest logit
FUSE_LOGITS_RTOL = 2e-2
GRAM_TOL = 1e-5


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileMeter:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit counts its retrieval time)."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return (self.seconds, self.hits, self.misses)

    def since(self, mark) -> str:
        s, h, m = mark
        return (f"compile {self.seconds - s:.2f}s "
                f"(cache hits {self.hits - h}, misses {self.misses - m})")


def _close_fp32(name, got, want) -> None:
    """max |got - want| <= FP32_TOL * max(1, max |want|).

    Normwise, not per element: at T=2048 a state entry sums 2048 products,
    and where they cancel, summation order alone moves it by more than
    1e-5 of its own (small) value.
    """
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    _check(got.shape == want.shape, f"{name}: shape {got.shape} != "
           f"{want.shape}")
    _check(bool(np.isfinite(got).all()), f"{name}: non-finite output")
    err = float(np.abs(got - want).max())
    scale = max(1.0, float(np.abs(want).max()))
    _log(f"  {name}: max |err| {err:.3e}, max |ref| {scale:.3e}")
    _check(err <= FP32_TOL * scale,
           f"{name}: fp32 parity {err:.3e} beyond {FP32_TOL} x {scale:.3e}")


# ---------------------------------------------------------------------------
# one chip: kernels
# ---------------------------------------------------------------------------
def kernel_parity(jax, meter) -> None:
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import registry
    from repro.kernels.rm_attention.ops import (
        rm_attention_fused_decode_step,
        rm_attention_fused_prefill,
    )
    from repro.kernels.rm_feature.ops import rm_feature_fused
    from repro.models.attention import rm_plan_for

    cfg = get_config(ARCH, attention_mode="rm")
    plan = rm_plan_for(cfg, HEAD_DIM)        # the plan the served model uses
    est = registry.get("rm")
    w, col_deg, col_scale = est.pack_fused(
        plan, est.init_params(plan, jax.random.PRNGKey(SEED)))
    deg = jnp.asarray(col_deg, jnp.int32)
    sc = jnp.asarray(col_scale, jnp.float32)
    _log(f"kernels: packed omegas {tuple(w.shape)} (max_degree, F, d)")
    keys = jax.random.split(jax.random.PRNGKey(SEED + 1), 8)

    def unit_rows(key, shape, norm=1.0):
        x = jax.random.normal(key, shape, jnp.float32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True) * norm

    # Both sides at full fp32 matmul precision, so the comparison is about
    # the kernels, not the MXU pass count XLA picks for a plain f32 dot.
    with jax.default_matmul_precision("highest"):
        mark = meter.mark()
        t0 = time.perf_counter()
        # rm_feature_fused on unit-ball rows (tests/test_precision.py)
        x = unit_rows(keys[0], (4096, HEAD_DIM), 0.8)
        want = rm_feature_fused(x, w, deg, sc, use_pallas=False)
        got = rm_feature_fused(x, w, deg, sc, use_pallas=True)
        _close_fp32(f"rm_feature_fused fp32 x[4096,128] w{tuple(w.shape)}",
                    got, want)
        z32 = want

    # bf16 operands at the precision policy's own setting: Mosaic refuses
    # an fp32 contract precision on bf16 operands
    got_b = rm_feature_fused(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                             deg, sc, use_pallas=True)
    err_b = float(jnp.max(jnp.abs(got_b - z32)))
    _log(f"  rm_feature_fused bf16: max |err| vs fp32 oracle {err_b:.3e} "
         f"(budget {RM_BF16_FEATURE_ATOL})")
    _check(err_b <= RM_BF16_FEATURE_ATOL,
           f"rm_feature_fused bf16 error {err_b:.3e} over budget")

    with jax.default_matmul_precision("highest"):
        # fused prefill at the largest bucket; the last 48 keys are padding
        shape = (1, HEADS, BUCKET, HEAD_DIM)
        q = unit_rows(keys[1], shape)
        k = unit_rows(keys[2], shape)
        v = jax.random.normal(keys[3], shape, jnp.float32)
        kvalid = (jnp.arange(BUCKET) < BUCKET - 48).astype(
            jnp.float32)[None]
        want = rm_attention_fused_prefill(q, k, v, w, col_deg, col_scale,
                                          kvalid=kvalid, use_pallas=False)
        got = rm_attention_fused_prefill(q, k, v, w, col_deg, col_scale,
                                         kvalid=kvalid, use_pallas=True)
        for name, g, r in zip(("out", "S", "n"), got, want):
            _close_fp32(f"rm_attention_fused_prefill {name} T={BUCKET}",
                        g, r)

        # fused decode step for 4 slots x 16 heads from a 64-token state
        ctx = (SLOTS, HEADS, 64, HEAD_DIM)
        _, s0, n0 = rm_attention_fused_prefill(
            unit_rows(keys[4], ctx), unit_rows(keys[5], ctx),
            jax.random.normal(keys[6], ctx, jnp.float32), w, col_deg,
            col_scale, use_pallas=False)
        row = (SLOTS, HEADS, HEAD_DIM)
        qk = jax.random.split(keys[7], 3)
        args = (unit_rows(qk[0], row), unit_rows(qk[1], row),
                jax.random.normal(qk[2], row, jnp.float32), s0, n0, w,
                col_deg, col_scale)
        want = rm_attention_fused_decode_step(*args, use_pallas=False)
        got = rm_attention_fused_decode_step(*args, use_pallas=True)
        for name, g, r in zip(("out", "S", "n"), got, want):
            _close_fp32(f"rm_attention_fused_decode_step {name} "
                        f"[{SLOTS}x{HEADS}]", g, r)
    _log(f"kernels: parity OK in {time.perf_counter() - t0:.1f}s, "
         f"{meter.since(mark)}")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def _prompts(vocab: int):
    import numpy as np

    rng = np.random.default_rng(SEED)
    return [rng.integers(0, vocab, size=n).astype(np.int32)
            for n in PROMPT_LENS]


def _serve_pass(engine, prompts, first_id: int):
    """Submit the prompts, run to completion, check every request."""
    from repro.serve import Request

    for i, p in enumerate(prompts):
        engine.submit(Request(request_id=first_id + i, prompt=p,
                              max_new_tokens=MAX_NEW))
    t0 = time.perf_counter()
    done = engine.run()
    wall = time.perf_counter() - t0
    out = {}
    for i in range(len(prompts)):
        state = done.get(first_id + i)
        _check(state is not None, f"request {first_id + i} did not finish")
        _check(state.finish_reason == "max_new_tokens",
               f"request {first_id + i}: finish reason "
               f"{state.finish_reason!r}")
        _check(len(state.generated) == MAX_NEW,
               f"request {first_id + i}: {len(state.generated)} tokens")
        out[i] = (list(state.generated),
                  state.t_first_token - state.t_enqueue)
    return out, wall


def _program_texts(engine, prompt):
    """Compiled text of the served prefill (bucket of ``prompt``) and decode
    programs — the persistent cache holds both after the first pass."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.executor import _decode_compiled, _prefill_compiled

    ex = engine.executor
    tb = ex.bucket_for(len(prompt))
    tokens = np.zeros((1, tb), np.int32)
    positions = np.full((1, tb), -1, np.int32)
    prefill_text = _prefill_compiled.lower(
        ex.params, ex.cfg, jnp.asarray(tokens), jnp.asarray(positions),
        ex.max_len).compile().as_text()
    decode_text = _decode_compiled.lower(
        ex.params, ex.cfg, ex.cache,
        jnp.zeros((ex.num_slots, 1), jnp.int32),
        jnp.full((ex.num_slots,), ex.scratch_position, jnp.int32),
    ).compile().as_text()
    return prefill_text, decode_text


def serve_one_chip(jax, meter, mode: str) -> None:
    import numpy as np

    from repro.kernels.common import default_interpret
    from repro.launch.serve import make_engine
    from repro.models.attention import rm_fuse_enabled

    mark = meter.mark()
    t0 = time.perf_counter()
    engine = make_engine(ARCH, smoke=False, attention_mode=mode,
                         num_slots=SLOTS, max_len=MAX_LEN, seed=SEED)
    jax.block_until_ready(engine.params)
    cfg = engine.cfg
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(engine.params))
    _log(f"serve {mode}: {ARCH} FULL, {n_params / 1e9:.3f}B params, "
         f"{SLOTS} slots, max_len {MAX_LEN}, init "
         f"{time.perf_counter() - t0:.1f}s")
    _check(engine.max_restarts == 0, "serving must run with max_restarts=0")
    if mode == "rm":
        _check(not default_interpret(), "Pallas would run interpreted")
        _check(rm_fuse_enabled(cfg) and engine.fused_attention,
               "fuse_featurize='auto' did not fuse on the TPU")
    prompts = _prompts(cfg.vocab_size)
    buckets = sorted({engine.executor.bucket_for(len(p)) for p in prompts})
    _check(len(buckets) >= 3, f"prompts hit buckets {buckets}")

    cold, cold_wall = _serve_pass(engine, prompts, 0)
    _log(f"serve {mode}: cold pass {cold_wall:.1f}s over buckets {buckets}, "
         f"{meter.since(mark)}")
    warm, warm_wall = _serve_pass(engine, prompts, len(prompts))
    ttft = sorted(t for _, t in warm.values())
    _log(f"serve {mode}: warm pass {warm_wall:.2f}s, "
         f"{len(prompts) * MAX_NEW / warm_wall:.1f} tok/s, TTFT p50 "
         f"{ttft[len(ttft) // 2]:.3f}s max {ttft[-1]:.3f}s, tokens same as "
         f"cold pass: {all(cold[i][0] == warm[i][0] for i in cold)}")

    if mode == "rm":
        prefill_text, decode_text = _program_texts(engine, prompts[-1])
        _check("tpu_custom_call" in prefill_text,
               "rm prefill program has no Pallas kernel")
        _check("tpu_custom_call" in decode_text,
               "rm decode program has no Pallas kernel")
        _log("serve rm: compiled prefill and decode hold tpu_custom_call")
        fuse_vs_two_launch(engine, prompts[3])
    del engine
    gc.collect()


def fuse_vs_two_launch(engine, prompt) -> None:
    """Prefill logits at fuse_featurize='auto' (fused kernel) against 'off'
    (featurize launch + attention launch) on the same parameters."""
    import numpy as np

    from repro.serve.executor import StepExecutor

    ex = engine.executor
    cfg_off = dataclasses.replace(
        ex.cfg, rm=dataclasses.replace(ex.cfg.rm, fuse_featurize="off"))
    ex_off = StepExecutor(cfg_off, ex.params, 1, ex.max_len)
    _check(not ex_off.fused_attention, "'off' still fused")
    t = len(prompt)
    fused, _, tb = ex.prefill(prompt)
    two, _, _ = ex_off.prefill(prompt)
    fused = np.asarray(fused[0, :t], np.float32)
    two = np.asarray(two[0, :t], np.float32)
    _check(bool(np.isfinite(fused).all()), "non-finite fused logits")
    gap = float(np.abs(fused - two).max())
    scale = float(np.abs(two).max())
    _log(f"serve rm: fused vs two-launch prefill logits (T={t}, bucket "
         f"{tb}): max |gap| {gap:.3e}, max |logit| {scale:.3e}, "
         f"ratio {gap / scale:.3e} (limit {FUSE_LOGITS_RTOL})")
    _check(gap <= FUSE_LOGITS_RTOL * scale,
           "fused prefill logits disagree with the two-launch path")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def _spread_over(jax, tree, devices, what: str) -> None:
    for leaf in jax.tree_util.tree_leaves(tree):
        got = {s.device for s in leaf.addressable_shards}
        _check(got == set(devices),
               f"{what} leaf {leaf.shape} lives on {len(got)} device(s)")


def _compare(name, a, b) -> int:
    """Log, per request, whether runs ``a`` and ``b`` generated the same
    tokens and the logit gap of their decode steps up to the request's own
    first divergent token: until then both runs fed the request the same
    tokens, whatever its neighbours did, so the gap is numerics alone.
    Returns the number of token-identical requests."""
    import numpy as np

    (toks_a, rows_a), (toks_b, rows_b) = a, b
    same, gap, scale = 0, 0.0, 0.0
    for r in sorted(toks_a):
        ta, tb = toks_a[r], toks_b[r]
        _check(len(rows_a[r]) == len(rows_b[r]) == len(ta) - 1,
               f"{name}: request {r} has a decode step unrecorded")
        first = next((k for k, (x, y) in enumerate(zip(ta, tb)) if x != y),
                     None)
        _check(first != 0, f"{name}: request {r} differs at its prefill "
               "token")
        # rows[j] are the logits that chose token j + 1
        upto = len(ta) - 1 if first is None else first
        gaps = [float(np.abs(x - y).max())
                for x, y in zip(rows_a[r][:upto], rows_b[r][:upto])]
        gap = max(gap, *gaps)
        scale = max(scale, *(float(np.abs(x).max())
                             for x in rows_a[r][:upto]))
        if first is None:
            same += 1
        else:
            _log(f"{name}: request {r} diverges at token {first}, logit "
                 f"gap there {gaps[-1]:.3e}")
    _log(f"{name}: {same}/{len(toks_a)} requests token-identical; max "
         f"logit gap up to each request's first divergence {gap:.3e} (max "
         f"|logit| {scale:.3e})")
    return same


def dp_decode(jax, meter) -> None:
    """DP decode over a (4, 1) mesh with 8 slots (2 lanes per device)
    against one device with 2 slots, the per-device batch: every request's
    tokens must match. The gaps to one device with 8 slots are logged
    beside the gap that batch shape alone leaves on one device."""
    import numpy as np

    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import make_engine

    results = {}
    for label, slots, on_mesh in (("one device, 8 slots", 8, False),
                                  ("one device, 2 slots", 2, False),
                                  ("DP (4,1) mesh, 8 slots", 8, True)):
        mark = meter.mark()
        mesh = None
        if on_mesh:
            mesh = make_host_mesh()
            _check(dict(mesh.shape) == {"data": 4, "model": 1},
                   f"host mesh {dict(mesh.shape)}")
        engine = make_engine(ARCH, smoke=False, attention_mode="rm",
                             num_slots=slots, max_len=MAX_LEN, seed=SEED,
                             mesh=mesh)
        prompts = _prompts(engine.cfg.vocab_size)
        if mesh is not None:
            devices = jax.devices()
            _spread_over(jax, engine.params, devices, "param")
            _spread_over(jax, engine.cache, devices, "cache")
            for leaf in jax.tree_util.tree_leaves(engine.cache):
                _check(max(s.data.size for s in leaf.addressable_shards)
                       * len(devices) == leaf.size,
                       f"cache leaf {leaf.shape} is not split over slots")
            # every device holds a full replica of the parameters
            param_gib = sum(a.nbytes for a in jax.tree_util.tree_leaves(
                engine.params)) / 2**30
            used = [(d.memory_stats() or {}).get("bytes_in_use", 0) / 2**30
                    for d in devices]
            _log(f"dp: params {param_gib:.2f} GiB per replica; GiB in use "
                 "per device " + ", ".join(f"{u:.2f}" for u in used))
            if devices[0].platform == "tpu":
                _check(min(used) >= param_gib,
                       "a device holds less than one parameter replica")
        # every decode step's logits, per request: the runs' schedules
        # differ (2 slots take four waves), a request's tokens do not
        rows = {i: [] for i in range(len(prompts))}
        decode = engine.executor.decode

        def recording(tokens, positions, decode=decode, engine=engine,
                      rows=rows):
            logits = decode(tokens, positions)
            step = np.asarray(logits[:, 0], np.float32)
            for lane, state in enumerate(engine.slots):
                if state is not None:
                    rows[state.request.request_id].append(step[lane])
            return logits

        engine.executor.decode = recording
        out, wall = _serve_pass(engine, prompts, 0)
        results[label] = ({i: out[i][0] for i in out}, rows)
        _log(f"dp: {label}: {wall:.1f}s, {meter.since(mark)}")
        del engine
        gc.collect()
    wide, narrow, dp = results.values()
    _compare("dp: one device 2 slots vs 8 slots", wide, narrow)
    _compare("dp: DP vs one device 8 slots", wide, dp)
    same = _compare("dp: DP vs one device 2 slots", narrow, dp)
    _check(same == len(PROMPT_LENS), "DP decode tokens differ from one "
           "device at the same per-device batch")


def sharded_gram(jax, meter) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ExponentialDotProductKernel, make_feature_map
    from repro.launch.mesh import make_feature_mesh

    mark = meter.mark()
    mesh = make_feature_mesh(4)
    fm = make_feature_map(ExponentialDotProductKernel(1.0), HEAD_DIM, 1024,
                          jax.random.PRNGKey(SEED), estimator="rm",
                          measure="proportional", n_max=8, mesh=mesh)
    _check(fm.num_shards == 4, f"{fm.num_shards} feature shards")
    x = jax.random.normal(jax.random.PRNGKey(SEED + 1), (4096, HEAD_DIM))
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True) * 0.8
    with jax.default_matmul_precision("highest"):
        g_mesh = np.asarray(fm.estimate_gram(x, sharded=True))
        g_ref = np.asarray(fm.estimate_gram(x, sharded=False))
    err = float(np.abs(g_mesh - g_ref).max())
    _log(f"gram: 4 rm_features shards x {fm.shard_output_dim} features, "
         f"[4096, 4096] Gram: max |sharded - single| {err:.3e} "
         f"(limit {GRAM_TOL}), {meter.since(mark)}")
    _check(err <= GRAM_TOL, "sharded Gram disagrees with single device")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels + serving; 4: DP decode + sharded "
                         "Gram only")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.common import env

    cache_dir = env.use_compile_cache()
    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX backend is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 1
    meter = CompileMeter(jax)
    _log(f"provenance {json.dumps(env.platform_provenance())}")
    _log(f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 1:
        kernel_parity(jax, meter)
        serve_one_chip(jax, meter, "rm")
        serve_one_chip(jax, meter, "exact")
    else:
        failed = []
        for phase in (dp_decode, sharded_gram):
            try:
                phase(jax, meter)
            except SmokeFailure as e:   # run the other phase, then fail
                _log(f"FAILED {phase.__name__}: {e}")
                failed.append(phase.__name__)
        _check(not failed, f"failed phases: {failed}")
    _log(f"total {time.perf_counter() - t0:.1f}s, compile "
         f"{meter.seconds:.1f}s, cache hits {meter.hits}, misses "
         f"{meter.misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
