"""Shared tiling + launch-default helpers for the Pallas kernel wrappers.

Three responsibilities, shared by ALL kernel families so a tune or a policy
change lands everywhere at once:

* ``default_interpret()`` — the ONE backend-detection rule deciding whether
  a launch runs the Pallas interpreter (off-TPU) or compiles (TPU). The
  rm/sketch/ctr/attention ops wrappers all resolve ``interpret=None``
  through this function instead of each repeating the backend check.
* VMEM-budget tile heuristics — ``pick_feature_blocks`` for the
  (batch, feature)-tiled kernels (rm_feature, ctr_feature) and
  ``pick_batch_block`` for the batch-only-tiled TensorSketch kernel. Both
  are dtype-aware: bf16 inputs halve the x/weight working set, so the
  heuristic can afford larger tiles at the same budget (accumulators are
  always fp32 — see repro.common.dtypes.Precision).
* The measured ladder autotuner — ``autotune_feature_blocks`` times real
  launches over the feasible ladder and persists the winner in a
  per-(kernel, shape, dtype, backend) JSON cache; ``get_feature_blocks`` /
  ``get_batch_block`` consult that cache before falling back to the
  heuristic. Lookups are pure host-side dict reads, so they are safe at
  trace time; MEASURING only happens through the explicit autotune entry
  points (``python -m repro.bench --autotune`` drives them), never inside
  a jitted apply.

v5e has ~128MiB of VMEM per core; we budget well under it to leave room
for double buffering.
"""
from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs import clock as _obs_clock

VMEM_BUDGET = 12 * 1024 * 1024  # bytes

# Candidate (block_b, block_f) tiles, largest first — shared by every
# (batch, feature)-tiled feature-map kernel so a ladder tune lands on all
# of them at once.
_BLOCK_LADDER = ((512, 256), (256, 256), (256, 128), (128, 128), (128, 64),
                 (64, 64), (32, 32), (16, 16), (8, 8))

# Batch-tile ladder for kernels that keep the whole feature axis resident
# (tensor_sketch).
_BATCH_LADDER = (512, 256, 128, 64, 32, 16, 8)

# (chunk, block_f) ladder for the fused featurize+attention kernels
# (kernels/rm_attention/fused.py): the chunk axis tiles the sequence, the
# feature axis tiles the packed omega layout. Largest first.
_ATTN_LADDER = ((256, 256), (128, 256), (128, 128), (64, 128), (64, 64),
                (32, 64), (32, 32), (16, 16), (8, 8))


def default_interpret() -> bool:
    """The one backend-detection rule for Pallas launches.

    Off-TPU backends run the Pallas interpreter (a correctness harness, not
    a performance target); on TPU the kernels compile. Every ops wrapper
    resolves ``interpret=None`` through this function — tests monkeypatch
    it to steer all launches at once.
    """
    return jax.default_backend() != "tpu"


def round_up(x: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``x``."""
    return (x + m - 1) // m * m


def dtype_itemsize(dtype) -> int:
    """Bytes per element for a dtype name / jnp dtype (bf16 -> 2)."""
    return int(jnp.dtype(dtype).itemsize)


# ---------------------------------------------------------------------------
# VMEM-budget heuristics (the autotuner's fallback)
# ---------------------------------------------------------------------------
def pick_feature_blocks(
    d: int,
    depth: int,
    b: int,
    f: int,
    *,
    weight_tensors: int = 1,
    accumulators: int = 2,
    itemsize: int = 4,
) -> Tuple[int, int]:
    """Largest (block_b, block_f) tile whose working set fits VMEM.

    Shared by the (batch, feature)-tiled feature-map kernels
    (``rm_feature``: one packed weight tensor, two [bm, bf] live buffers;
    ``ctr_feature``: two weight tensors for the complex pair, four
    buffers). Working set per tile: x and the packed weights at
    ``itemsize`` bytes/element (2 for bf16 inputs), accumulators always
    fp32:

        itemsize * (bm*d + weight_tensors * depth*bf*d)
            + 4 * accumulators * bm*bf.
    """
    for bm, bf in _BLOCK_LADDER:
        if bm > max(b, 8) * 2 or bf > max(f, 8) * 2:
            continue
        working = (itemsize * (bm * d + weight_tensors * depth * bf * d)
                   + 4 * accumulators * bm * bf)
        if working <= VMEM_BUDGET:
            return bm, bf
    return 8, 8


def pick_batch_block(
    d: int,
    depth: int,
    fs: int,
    b: int,
    *,
    itemsize: int = 4,
) -> int:
    """Largest batch tile for the whole-feature-axis-resident kernels.

    Working set (tensor_sketch): x tile + both packed weight tensors +
    both inverse-DFT matrices at ``itemsize`` bytes, three [bm, Fs] live
    fp32 accumulators (out, ar/ai).
    """
    fixed = itemsize * (2 * depth * fs * d + 2 * fs * fs)
    for bm in _BATCH_LADDER:
        if bm > max(b, 8) * 2:
            continue
        if fixed + itemsize * bm * d + 4 * bm * 3 * fs <= VMEM_BUDGET:
            return bm
    return 8


# ---------------------------------------------------------------------------
# persistent per-(kernel, shape, dtype, backend) block cache
# ---------------------------------------------------------------------------
_CACHE_ENV = "REPRO_BLOCK_CACHE"
# Inside the checkout, so only a committed file can steer which tiles the
# main path compiles; absent -> the VMEM heuristic.
_DEFAULT_CACHE = Path(__file__).resolve().parents[3] / "feature_blocks.json"

_block_cache: Optional[Dict[str, list]] = None
_block_cache_path: Optional[Path] = None


def block_cache_path() -> Path:
    """Where the measured-block cache lives (override: $REPRO_BLOCK_CACHE)."""
    return Path(os.environ.get(_CACHE_ENV, _DEFAULT_CACHE)).expanduser()


def cache_key(kernel: str, d: int, depth: int, b: int, f: int,
              dtype) -> str:
    """One cache row per (kernel family, shape, input dtype, backend).

    Key schema (feature-map kernels, value ``[block_b, block_f]`` — the
    batch-only-tiled kernels store ``[block_b, block_b]``)::

        {kernel}/d{input_dim}/k{max_degree}/b{batch}/f{features}/{dtype}/{backend}

    e.g. ``rm_feature/d64/k8/b4096/f256/float32/tpu``. The attention-fused
    kernels use the richer :func:`attention_cache_key` schema; the two key
    families share one JSON file (``$REPRO_BLOCK_CACHE``) and cannot
    collide because the attention keys carry ``t{...}``/``v{...}`` fields.
    """
    name = jnp.dtype(dtype).name
    return (f"{kernel}/d{d}/k{depth}/b{b}/f{f}/{name}/"
            f"{jax.default_backend()}")


def attention_cache_key(kernel: str, d: int, depth: int, t: int, f: int,
                        dv: int, dtype) -> str:
    """Cache row for the fused featurize+attention kernels.

    Key schema (value is the measured ``[chunk, block_f]`` pair)::

        {kernel}/d{head_dim}/k{max_degree}/t{seq_len}/f{features}/v{value_dim}/{dtype}/{backend}

    e.g. ``rm_attn_fused/d64/k8/t1024/f256/v64/bfloat16/tpu``. ``t`` and
    ``dv`` are part of the key because the score tile ([chunk, chunk]) and
    the state scratch (f * dv) dominate the fused kernel's VMEM working
    set, so the best tile genuinely shifts with them.
    """
    name = jnp.dtype(dtype).name
    return (f"{kernel}/d{d}/k{depth}/t{t}/f{f}/v{dv}/{name}/"
            f"{jax.default_backend()}")


def load_block_cache(path: Optional[Path] = None) -> Dict[str, list]:
    """Read (and memoize) the persisted cache; missing/corrupt -> empty."""
    global _block_cache, _block_cache_path
    p = Path(path) if path is not None else block_cache_path()
    if _block_cache is not None and _block_cache_path == p:
        return _block_cache
    cache: Dict[str, list] = {}
    try:
        cache = json.loads(p.read_text())
        if not isinstance(cache, dict):
            cache = {}
    except (OSError, ValueError):
        cache = {}
    _block_cache, _block_cache_path = cache, p
    return cache


def save_block_cache(cache: Dict[str, list],
                     path: Optional[Path] = None) -> Path:
    """Persist the cache (and refresh the in-process memo)."""
    global _block_cache, _block_cache_path
    p = Path(path) if path is not None else block_cache_path()
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(cache, indent=2, sort_keys=True))
    _block_cache, _block_cache_path = dict(cache), p
    return p


def clear_block_cache_memo() -> None:
    """Drop the in-process memo (tests point $REPRO_BLOCK_CACHE elsewhere)."""
    global _block_cache, _block_cache_path
    _block_cache = None
    _block_cache_path = None


def get_feature_blocks(
    kernel: str,
    d: int,
    depth: int,
    b: int,
    f: int,
    *,
    dtype=jnp.float32,
    weight_tensors: int = 1,
    accumulators: int = 2,
) -> Tuple[int, int]:
    """Measured blocks if the cache has this shape, else the heuristic.

    The lookup is a host-side dict read — safe inside a jit trace (shapes
    are static there). All three fused wrappers route through here, so one
    ``autotune`` pass (or a shipped cache file) retargets every launch.
    """
    hit = load_block_cache().get(cache_key(kernel, d, depth, b, f, dtype))
    if hit is not None and len(hit) == 2:
        return int(hit[0]), int(hit[1])
    return pick_feature_blocks(
        d, depth, b, f, weight_tensors=weight_tensors,
        accumulators=accumulators, itemsize=dtype_itemsize(dtype),
    )


def get_batch_block(
    kernel: str,
    d: int,
    depth: int,
    fs: int,
    b: int,
    *,
    dtype=jnp.float32,
) -> int:
    """Batch-tile variant of ``get_feature_blocks`` (tensor_sketch)."""
    hit = load_block_cache().get(cache_key(kernel, d, depth, b, fs, dtype))
    if hit is not None and len(hit) == 2:
        return int(hit[0])
    return pick_batch_block(d, depth, fs, b,
                            itemsize=dtype_itemsize(dtype))


# ---------------------------------------------------------------------------
# fused featurize+attention (chunk, feature-block) tiles
# ---------------------------------------------------------------------------
def _attention_working_set(d: int, depth: int, f: int, dv: int, c: int,
                           bf: int, itemsize: int) -> int:
    """VMEM bytes for one fused-attention program at tile (chunk=c, bf).

    Streamed operands at input itemsize (q, k chunks + v chunk + the packed
    omega block), fp32 live tiles (zq, zk, score [c, c], num/den), and the
    fp32 state scratch over the WHOLE padded feature axis (it persists
    across the chunk sweep — see fused.py docstring).
    """
    f_pad = round_up(max(f, 1), bf)
    streamed = itemsize * (2 * c * d + c * dv + depth * bf * d)
    live = 4 * (2 * c * bf + c * c + c * dv + c)
    state = 4 * (f_pad * dv + f_pad)
    return streamed + live + state


def pick_attention_blocks(
    d: int,
    depth: int,
    t: int,
    f: int,
    dv: int,
    *,
    itemsize: int = 4,
) -> Tuple[int, int]:
    """Largest feasible (chunk, block_f) for the fused attention kernels."""
    for c, bf in _ATTN_LADDER:
        if c > max(t, 8) * 2 or bf > max(f, 8) * 2:
            continue
        if _attention_working_set(d, depth, f, dv, c, bf,
                                  itemsize) <= VMEM_BUDGET:
            return c, bf
    return 8, 8


def feasible_attention_blocks(
    d: int,
    depth: int,
    t: int,
    f: int,
    dv: int,
    *,
    itemsize: int = 4,
) -> Tuple[Tuple[int, int], ...]:
    """Ladder candidates whose fused-attention working set fits VMEM."""
    out = []
    for c, bf in _ATTN_LADDER:
        if c > max(t, 8) * 2 or bf > max(f, 8) * 2:
            continue
        if _attention_working_set(d, depth, f, dv, c, bf,
                                  itemsize) <= VMEM_BUDGET:
            out.append((c, bf))
    return tuple(out) or ((8, 8),)


def get_attention_blocks(
    kernel: str,
    *,
    d: int,
    depth: int,
    t: int,
    f: int,
    dv: int,
    dtype=jnp.float32,
) -> Tuple[int, int]:
    """Measured (chunk, block_f) if cached, else the VMEM heuristic.

    Same contract as ``get_feature_blocks``: a pure host-side dict read
    keyed by :func:`attention_cache_key`, safe at trace time; measurement
    only happens via :func:`autotune_attention_blocks`.
    """
    hit = load_block_cache().get(
        attention_cache_key(kernel, d, depth, t, f, dv, dtype))
    if hit is not None and len(hit) == 2:
        return int(hit[0]), int(hit[1])
    return pick_attention_blocks(d, depth, t, f, dv,
                                 itemsize=dtype_itemsize(dtype))


def autotune_attention_blocks(
    kernel: str,
    launch: Callable[[int, int], object],
    *,
    d: int,
    depth: int,
    t: int,
    f: int,
    dv: int,
    dtype=jnp.float32,
    candidates: Optional[Iterable[Tuple[int, int]]] = None,
    repeats: int = 3,
    path: Optional[Path] = None,
) -> Tuple[int, int]:
    """Measured-ladder tune for the fused attention kernels.

    ``launch(chunk, block_f)`` must run the real fused kernel end-to-end;
    the median-of-``repeats`` winner is persisted under
    :func:`attention_cache_key` in the same ``$REPRO_BLOCK_CACHE`` file the
    feature-map kernels use. Host-side offline pass only (driven by
    ``python -m repro.bench --autotune``).
    """
    cands = tuple(candidates) if candidates is not None else \
        feasible_attention_blocks(d, depth, t, f, dv,
                                  itemsize=dtype_itemsize(dtype))
    best, best_t = None, float("inf")
    for c, bf in cands:
        try:
            tm = _median_seconds(lambda: launch(c, bf), repeats)
        except Exception:  # infeasible tile (e.g. VMEM OOM on TPU): skip
            continue
        if tm < best_t:
            best, best_t = (c, bf), tm
    if best is None:
        best = pick_attention_blocks(d, depth, t, f, dv,
                                     itemsize=dtype_itemsize(dtype))
    cache = dict(load_block_cache(path))
    cache[attention_cache_key(kernel, d, depth, t, f, dv, dtype)] = \
        list(best)
    save_block_cache(cache, path)
    return best


# ---------------------------------------------------------------------------
# measured ladder autotune
# ---------------------------------------------------------------------------
def feasible_feature_blocks(
    d: int,
    depth: int,
    b: int,
    f: int,
    *,
    weight_tensors: int = 1,
    accumulators: int = 2,
    itemsize: int = 4,
) -> Tuple[Tuple[int, int], ...]:
    """The ladder candidates whose working set fits VMEM for this shape."""
    out = []
    for bm, bf in _BLOCK_LADDER:
        if bm > max(b, 8) * 2 or bf > max(f, 8) * 2:
            continue
        working = (itemsize * (bm * d + weight_tensors * depth * bf * d)
                   + 4 * accumulators * bm * bf)
        if working <= VMEM_BUDGET:
            out.append((bm, bf))
    return tuple(out) or ((8, 8),)


def _median_seconds(fn: Callable[[], object], repeats: int) -> float:
    # warm up / compile outside the timed region — and BLOCK on it, so the
    # async warm-up tail can't bleed into the first timed repeat. Timing
    # reads the shared obs monotonic clock (repro.obs.clock), the same
    # instrument behind bench timings and serving latencies.
    jax.block_until_ready(fn())
    times = []
    for _ in range(repeats):
        t0 = _obs_clock.monotonic()
        out = fn()
        jax.block_until_ready(out)
        times.append(_obs_clock.monotonic() - t0)
    return sorted(times)[len(times) // 2]


def autotune_feature_blocks(
    kernel: str,
    launch: Callable[[int, int], object],
    d: int,
    depth: int,
    b: int,
    f: int,
    *,
    dtype=jnp.float32,
    weight_tensors: int = 1,
    accumulators: int = 2,
    candidates: Optional[Iterable[Tuple[int, int]]] = None,
    repeats: int = 3,
    path: Optional[Path] = None,
) -> Tuple[int, int]:
    """Time ``launch(block_b, block_f)`` over the ladder; persist the winner.

    ``launch`` must run the REAL kernel end-to-end with the given blocks
    and return its (jax) result; each candidate is warmed once (compile)
    then timed ``repeats`` times, median wins. The winning pair lands in
    the persistent cache under this (kernel, shape, dtype, backend) key so
    every later ``get_feature_blocks`` call — in any process on the same
    cache — uses the measured tiles. This is a HOST-side offline pass:
    never call it from inside a jitted function.
    """
    cands = tuple(candidates) if candidates is not None else \
        feasible_feature_blocks(
            d, depth, b, f, weight_tensors=weight_tensors,
            accumulators=accumulators, itemsize=dtype_itemsize(dtype),
        )
    best, best_t = None, float("inf")
    for bm, bf in cands:
        try:
            t = _median_seconds(lambda: launch(bm, bf), repeats)
        except Exception:  # infeasible tile (e.g. VMEM OOM on TPU): skip
            continue
        if t < best_t:
            best, best_t = (bm, bf), t
    if best is None:
        best = pick_feature_blocks(
            d, depth, b, f, weight_tensors=weight_tensors,
            accumulators=accumulators, itemsize=dtype_itemsize(dtype),
        )
    cache = dict(load_block_cache(path))
    cache[cache_key(kernel, d, depth, b, f, dtype)] = list(best)
    save_block_cache(cache, path)
    return best
