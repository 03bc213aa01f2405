"""Pallas TPU kernels: FUSED featurize+attention over the packed RM layout.

The two-launch pipeline (``rm_feature_fused`` -> ``rm_attention``) pays an
O(T * F) HBM round-trip for Z(q) and Z(k) between the launches. The math
says we shouldn't: each attention tile only ever needs the slice of
Z it is currently contracting, and that slice is a masked running product
over the packed ``[max_degree, F, d]`` omega tensor (DESIGN.md §3) — cheap
enough to recompute in VMEM. These kernels tile the featurize step INTO the
attention grid, so q/k/v stream from HBM once and Z never leaves VMEM
(DESIGN.md §13).

Three kernels share the in-VMEM featurize helper:

``rm_fused_attention_pallas`` — causal chunked linear attention. Grid
``(BH, nchunks, nfb)`` with the feature-block axis innermost; per program
(b, i, j) it featurizes chunk i of q and k against feature block j (masked
running product, fp32 accumulators per the precision policy), accumulates
the chunk-local score tile ``zq_ij zk_ij^T`` and the cross-chunk
numerator/denominator contributions ``zq_ij S_j`` / ``zq_ij n_j``, then
folds chunk i into the per-feature-block state scratch (``S_j += zk^T v``).
The state scratch persists across the chunk axis (sequential TPU grid), so
the inter-chunk prefix sum that the two-launch path computes in XLA happens
in VMEM for free; the last chunk also emits the final (S, n) — prefill gets
its decode state from the SAME launch.

``rm_fused_state_pallas`` — (k, v) -> final (S, n) only (non-causal
denominators, standalone state builds). Chunk axis innermost so the state
scratch is one ``[BF, dv]`` tile.

``rm_fused_apply_pallas`` — q + (S, n) -> output (the non-causal apply /
a fused one-shot decode over a batch of queries).

VMEM working set of the causal kernel (fp32): 2*C*d (q, k chunk) + C*dv (v)
+ depth*BF*d (w block) + C*C (scores) + C*dv + C (num/den) + F_pad*dv +
F_pad (state scratch, the WHOLE padded feature axis). E.g. C=128, F=256,
d=64, dv=64, depth 4: ~0.45 MB — the state scratch is the new term and
stays tiny because linear-attention state is O(F * dv), not O(T).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _featurize_block(x, w_ref, deg, scale):
    """Z slice for one (rows, feature-block) tile, entirely in registers/VMEM.

    ``x [C, d]`` stays in its stored dtype (bf16 under the mixed policy);
    every dot carries ``preferred_element_type=float32`` and the running
    product accumulates in fp32 — bf16-in / fp32-accum, never bf16
    accumulation (the same contract as ``kernels/rm_feature``).
    """
    c = x.shape[0]
    bf = deg.shape[-1]

    def step(j, acc):
        w = w_ref[pl.ds(j, 1), :, :]
        w = w.reshape(w.shape[1], w.shape[2])          # [bf, d]
        pj = jax.lax.dot_general(
            x, w,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [c, bf]
        return jnp.where(j < deg, acc * pj, acc)

    depth = jnp.max(deg)                               # tile-local depth
    acc = jax.lax.fori_loop(0, depth, step, jnp.ones((c, bf), jnp.float32))
    return acc * scale.astype(jnp.float32)


def _clamp(den, eps):
    return jnp.where(jnp.abs(den) < eps, jnp.where(den >= 0, eps, -eps), den)


# ---------------------------------------------------------------------------
# fused causal attention (+ final state)
# ---------------------------------------------------------------------------
def _fused_causal_kernel(q_ref, k_ref, v_ref, kval_ref, w_ref, deg_ref,
                         scale_ref, o_ref, s_ref, n_ref,
                         score_scr, num_scr, den_scr, s_scr, n_scr, *,
                         eps: float, nchunks: int, nfb: int):
    i = pl.program_id(1)                               # chunk
    j = pl.program_id(2)                               # feature block

    # new (batch*head) row: the state scratch restarts from zero. j is
    # innermost, so (i == 0, j == 0) runs before any other cell of this row.
    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _zero_state():
        s_scr[...] = jnp.zeros_like(s_scr)
        n_scr[...] = jnp.zeros_like(n_scr)

    # new chunk: reset the per-chunk accumulators.
    @pl.when(j == 0)
    def _zero_chunk():
        score_scr[...] = jnp.zeros_like(score_scr)
        num_scr[...] = jnp.zeros_like(num_scr)
        den_scr[...] = jnp.zeros_like(den_scr)

    deg = deg_ref[...]                                 # [1, bf]
    scale = scale_ref[...]
    zq = _featurize_block(q_ref[0], w_ref, deg, scale)        # [C, bf] f32
    zk = _featurize_block(k_ref[0], w_ref, deg, scale)
    zk = zk * kval_ref[0].astype(jnp.float32)                 # [C, 1] mask

    # chunk-local scores accumulate over feature blocks; the causal mask is
    # feature-independent, so it is applied once at finalize.
    score_scr[...] += jax.lax.dot_general(
        zq, zk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    # cross-chunk contribution reads the state BEFORE chunk i is folded in
    # (the state scratch holds chunks < i for this feature block).
    s_j = s_scr[pl.ds(j, 1), :, :][0]
    n_j = n_scr[pl.ds(j, 1), :]                        # [1, bf]
    num_scr[...] += jax.lax.dot_general(
        zq, s_j, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    den_scr[...] += jax.lax.dot_general(
        zq, n_j, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    v = v_ref[0].astype(jnp.float32)                   # [C, dv]
    s_new = s_j + jax.lax.dot_general(
        zk, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                                  # [bf, dv]
    n_new = n_j + jnp.sum(zk, axis=0, keepdims=True)   # [1, bf]
    s_scr[pl.ds(j, 1), :, :] = s_new[None]
    n_scr[pl.ds(j, 1), :] = n_new

    # last feature block: mask, combine intra-chunk and carried terms, emit.
    @pl.when(j == nfb - 1)
    def _emit_out():
        c = score_scr.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        scores = jnp.where(row >= col, score_scr[...], 0.0)
        num = num_scr[...] + jax.lax.dot_general(
            scores, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        den = den_scr[...] + jnp.sum(scores, axis=-1, keepdims=True)
        o_ref[0] = (num / _clamp(den, eps)).astype(o_ref.dtype)

    # last chunk: the state scratch now holds the full-prefix (S, n).
    @pl.when(i == nchunks - 1)
    def _emit_state():
        s_ref[0] = s_new.astype(s_ref.dtype)
        n_ref[0] = jnp.transpose(n_new, (1, 0)).astype(n_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk", "block_f", "eps", "interpret")
)
def rm_fused_attention_pallas(
    q: jax.Array,          # [BH, T, d]   (T % chunk == 0; pre-scaled inputs)
    k: jax.Array,          # [BH, T, d]
    v: jax.Array,          # [BH, T, dv]
    kvalid: jax.Array,     # [BH, T, 1]   1.0 real key / 0.0 padding
    w: jax.Array,          # [kdeg, F_pad, d] packed omegas (F_pad % block_f == 0)
    col_deg: jax.Array,    # [F_pad] int32  (padding columns: 0)
    col_scale: jax.Array,  # [F_pad] float32 (padding columns: 0)
    *,
    chunk: int,
    block_f: int,
    eps: float = 1e-4,
    interpret: bool = False,
):
    """Causal fused featurize+attention; returns (out, s_final, n_final).

    ``out [BH, T, dv]`` matches the two-launch composition
    ``rm_attention_causal(rm_feature_fused(q), rm_feature_fused(k) * kvalid,
    v)``; ``s_final [BH, F_pad, dv]`` / ``n_final [BH, F_pad, 1]`` are the
    whole-prefix linear-attention state (what
    ``rm_attention_prefill_final_state`` computes) from the same launch.
    """
    bh, t, d = q.shape
    dv = v.shape[-1]
    kdeg, f_pad, _ = w.shape
    assert t % chunk == 0, (t, chunk)
    assert f_pad % block_f == 0, (f_pad, block_f)
    nchunks = t // chunk
    nfb = f_pad // block_f
    grid = (bh, nchunks, nfb)
    kernel = functools.partial(
        _fused_causal_kernel, eps=eps, nchunks=nchunks, nfb=nfb
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, chunk, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((kdeg, block_f, d), lambda b, i, j: (0, j, 0)),
            pl.BlockSpec((1, block_f), lambda b, i, j: (0, j)),
            pl.BlockSpec((1, block_f), lambda b, i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_f, dv), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_f, 1), lambda b, i, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, f_pad, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, f_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((chunk, chunk), jnp.float32),
            pltpu.VMEM((chunk, dv), jnp.float32),
            pltpu.VMEM((chunk, 1), jnp.float32),
            pltpu.VMEM((nfb, block_f, dv), jnp.float32),
            pltpu.VMEM((nfb, block_f), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, kvalid, w, col_deg.reshape(1, f_pad),
      col_scale.reshape(1, f_pad))


# ---------------------------------------------------------------------------
# fused state build: (k, v) -> (S, n)
# ---------------------------------------------------------------------------
def _fused_state_kernel(k_ref, v_ref, kval_ref, w_ref, deg_ref, scale_ref,
                        s_ref, n_ref, s_scr, n_scr, *, nchunks: int):
    i = pl.program_id(2)                               # chunk (innermost)

    @pl.when(i == 0)
    def _zero():
        s_scr[...] = jnp.zeros_like(s_scr)
        n_scr[...] = jnp.zeros_like(n_scr)

    zk = _featurize_block(k_ref[0], w_ref, deg_ref[...], scale_ref[...])
    zk = zk * kval_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    s_scr[...] += jax.lax.dot_general(
        zk, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    n_scr[...] += jnp.sum(zk, axis=0, keepdims=True)

    @pl.when(i == nchunks - 1)
    def _emit():
        s_ref[0] = s_scr[...].astype(s_ref.dtype)
        n_ref[0] = jnp.transpose(n_scr[...], (1, 0)).astype(n_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk", "block_f", "interpret")
)
def rm_fused_state_pallas(
    k: jax.Array,          # [BH, T, d]
    v: jax.Array,          # [BH, T, dv]
    kvalid: jax.Array,     # [BH, T, 1]
    w: jax.Array,          # [kdeg, F_pad, d]
    col_deg: jax.Array,    # [F_pad] int32
    col_scale: jax.Array,  # [F_pad] float32
    *,
    chunk: int,
    block_f: int,
    interpret: bool = False,
):
    """(S, n) of the whole sequence without materializing Z(k) to HBM."""
    bh, t, d = k.shape
    dv = v.shape[-1]
    kdeg, f_pad, _ = w.shape
    assert t % chunk == 0 and f_pad % block_f == 0
    grid = (bh, f_pad // block_f, t // chunk)
    kernel = functools.partial(_fused_state_kernel, nchunks=t // chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((kdeg, block_f, d), lambda b, j, i: (0, j, 0)),
            pl.BlockSpec((1, block_f), lambda b, j, i: (0, j)),
            pl.BlockSpec((1, block_f), lambda b, j, i: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_f, dv), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_f, 1), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, f_pad, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, f_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_f, dv), jnp.float32),
            pltpu.VMEM((1, block_f), jnp.float32),
        ],
        interpret=interpret,
    )(k, v, kvalid, w, col_deg.reshape(1, f_pad),
      col_scale.reshape(1, f_pad))


# ---------------------------------------------------------------------------
# fused apply: q + (S, n) -> out
# ---------------------------------------------------------------------------
def _fused_apply_kernel(q_ref, s_in_ref, n_in_ref, w_ref, deg_ref, scale_ref,
                        o_ref, num_scr, den_scr, *, eps: float, nfb: int):
    j = pl.program_id(2)                               # feature block

    @pl.when(j == 0)
    def _zero():
        num_scr[...] = jnp.zeros_like(num_scr)
        den_scr[...] = jnp.zeros_like(den_scr)

    zq = _featurize_block(q_ref[0], w_ref, deg_ref[...], scale_ref[...])
    num_scr[...] += jax.lax.dot_general(
        zq, s_in_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    den_scr[...] += jax.lax.dot_general(
        zq, n_in_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == nfb - 1)
    def _emit():
        o_ref[0] = (num_scr[...] / _clamp(den_scr[...], eps)).astype(
            o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk", "block_f", "eps", "interpret")
)
def rm_fused_apply_pallas(
    q: jax.Array,          # [BH, T, d]
    s: jax.Array,          # [BH, F_pad, dv]
    n: jax.Array,          # [BH, F_pad, 1]
    w: jax.Array,          # [kdeg, F_pad, d]
    col_deg: jax.Array,    # [F_pad] int32
    col_scale: jax.Array,  # [F_pad] float32
    *,
    chunk: int,
    block_f: int,
    eps: float = 1e-4,
    interpret: bool = False,
) -> jax.Array:            # [BH, T, dv]
    """Featurize q in VMEM and contract it against a precomputed state."""
    bh, t, d = q.shape
    dv = s.shape[-1]
    kdeg, f_pad, _ = w.shape
    assert t % chunk == 0 and f_pad % block_f == 0
    nfb = f_pad // block_f
    grid = (bh, t // chunk, nfb)
    kernel = functools.partial(_fused_apply_kernel, eps=eps, nfb=nfb)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_f, dv), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_f, 1), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((kdeg, block_f, d), lambda b, i, j: (0, j, 0)),
            pl.BlockSpec((1, block_f), lambda b, i, j: (0, j)),
            pl.BlockSpec((1, block_f), lambda b, i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, dv), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((chunk, dv), jnp.float32),
            pltpu.VMEM((chunk, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, s, n, w, col_deg.reshape(1, f_pad), col_scale.reshape(1, f_pad))
