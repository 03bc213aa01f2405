"""Public jit'd RM-attention ops: causal (chunked Pallas forward + custom
VJP), non-causal (pure matmul), and the O(1)-state decode step.

The Pallas kernel has no automatic VJP, so ``rm_attention_causal`` is a
``jax.custom_vjp``: the forward runs the Pallas kernel, the backward
differentiates ``_causal_chunked_jnp`` — an algebraically identical chunked
formulation whose peak memory is O(T * chunk) instead of O(T^2).

Shapes use [B, H, T, F] features and [B, H, T, dv] values throughout.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.rm_attention.fused import (
    rm_fused_apply_pallas,
    rm_fused_attention_pallas,
    rm_fused_state_pallas,
)
from repro.kernels.rm_attention.ref import (
    _clamp_den,
    rm_attention_decode_ref,
    rm_attention_ref,
)
from repro.kernels.rm_attention.rm_attention import rm_attention_chunked_pallas
from repro.obs.trace import kernel_scope as _kernel_scope


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _chunk_states(zk_p, v_p, chunk):
    """Per-chunk key states + exclusive prefixes. zk_p: [B,H,T,F] padded."""
    b, h, t, f = zk_p.shape
    dv = v_p.shape[-1]
    n = t // chunk
    zk_c = zk_p.reshape(b, h, n, chunk, f).astype(jnp.float32)
    v_c = v_p.reshape(b, h, n, chunk, dv).astype(jnp.float32)
    s_chunk = jnp.einsum("bhncf,bhncd->bhnfd", zk_c, v_c)
    n_chunk = jnp.sum(zk_c, axis=3)
    s_prev = jnp.cumsum(s_chunk, axis=2) - s_chunk
    n_prev = jnp.cumsum(n_chunk, axis=2) - n_chunk
    return zk_c, v_c, s_prev, n_prev


def _pad_t(x, pad):
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


def _causal_chunked_jnp(zq, zk, v, chunk: int, eps: float):
    """Differentiable chunk-parallel causal linear attention (XLA path)."""
    b, h, t, f = zq.shape
    dv = v.shape[-1]
    chunk = min(chunk, t)
    pad = _round_up(t, chunk) - t
    zq_p, zk_p, v_p = _pad_t(zq, pad), _pad_t(zk, pad), _pad_t(v, pad)
    n = (t + pad) // chunk
    zq_c = zq_p.reshape(b, h, n, chunk, f).astype(jnp.float32)
    zk_c, v_c, s_prev, n_prev = _chunk_states(zk_p, v_p, chunk)

    scores = jnp.einsum("bhnqf,bhnkf->bhnqk", zq_c, zk_c)
    mask = jnp.tril(jnp.ones((chunk, chunk), dtype=bool))
    scores = jnp.where(mask, scores, 0.0)
    num = jnp.einsum("bhnqk,bhnkd->bhnqd", scores, v_c)
    num += jnp.einsum("bhnqf,bhnfd->bhnqd", zq_c, s_prev)
    den = jnp.sum(scores, axis=-1)
    den += jnp.einsum("bhnqf,bhnf->bhnq", zq_c, n_prev)
    den = _clamp_den(den, eps)
    out = num / den[..., None]
    return out.reshape(b, h, t + pad, dv)[:, :, :t]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _causal_pallas(zq, zk, v, chunk: int, eps: float, interpret: bool):
    b, h, t, f = zq.shape
    dv = v.shape[-1]
    chunk = min(chunk, t)
    pad = _round_up(t, chunk) - t
    zq_p, zk_p, v_p = _pad_t(zq, pad), _pad_t(zk, pad), _pad_t(v, pad)
    n = (t + pad) // chunk
    _, _, s_prev, n_prev = _chunk_states(zk_p, v_p, chunk)
    with _kernel_scope("rm_attention"):
        out = rm_attention_chunked_pallas(
            zq_p.reshape(b * h, t + pad, f),
            zk_p.reshape(b * h, t + pad, f),
            v_p.reshape(b * h, t + pad, dv),
            s_prev.reshape(b * h, n, f, dv),
            n_prev.reshape(b * h, n, f, 1),
            chunk=chunk,
            eps=eps,
            interpret=interpret,
        )
    return out.reshape(b, h, t + pad, dv)[:, :, :t]


def _causal_pallas_fwd(zq, zk, v, chunk, eps, interpret):
    return _causal_pallas(zq, zk, v, chunk, eps, interpret), (zq, zk, v)


def _causal_pallas_bwd(chunk, eps, interpret, res, g):
    zq, zk, v = res
    _, vjp = jax.vjp(
        lambda a, b_, c: _causal_chunked_jnp(a, b_, c, chunk, eps), zq, zk, v
    )
    return vjp(g.astype(jnp.float32))


_causal_pallas.defvjp(_causal_pallas_fwd, _causal_pallas_bwd)


def rm_attention_causal(
    zq: jax.Array,
    zk: jax.Array,
    v: jax.Array,
    *,
    chunk: int = 128,
    eps: float = 1e-4,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal linear attention, O(T * F * (C + dv)) work vs exact O(T^2 * dv).

    Pallas forward with a chunked-XLA custom VJP. ``use_pallas`` defaults to
    True on TPU and False elsewhere: interpret-mode Pallas unrolls the grid
    into the HLO, which is fine for kernel tests but would bloat dry-run
    compiles (tests opt in explicitly with use_pallas=True, interpret=True).
    """
    from repro.kernels.common import default_interpret

    if use_pallas is None:
        use_pallas = not default_interpret()
    if interpret is None:
        interpret = default_interpret()
    if not use_pallas:
        return _causal_chunked_jnp(zq, zk, v, chunk, eps)
    return _causal_pallas(zq, zk, v, chunk, eps, interpret)


def rm_attention_noncausal(
    zq: jax.Array,
    zk: jax.Array,
    v: jax.Array,
    *,
    eps: float = 1e-4,
) -> jax.Array:
    """Bidirectional linear attention: two GEMMs, no kernel needed."""
    zq = zq.astype(jnp.float32)
    zk = zk.astype(jnp.float32)
    v = v.astype(jnp.float32)
    s = jnp.einsum("bhsf,bhsd->bhfd", zk, v)           # [B,H,F,dv]
    n = jnp.sum(zk, axis=2)                            # [B,H,F]
    num = jnp.einsum("bhtf,bhfd->bhtd", zq, s)
    den = _clamp_den(jnp.einsum("bhtf,bhf->bht", zq, n), eps)
    return num / den[..., None]


def rm_attention_decode_step(
    zq: jax.Array,       # [B, H, F]
    zk: jax.Array,       # [B, H, F]
    v: jax.Array,        # [B, H, dv]
    state_s: jax.Array,  # [B, H, F, dv]
    state_n: jax.Array,  # [B, H, F]
    *,
    eps: float = 1e-4,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """O(1)-memory decode: rank-1 state update + two GEMVs.

    This is what replaces the growing KV cache for `long_500k` decoding.
    """
    return rm_attention_decode_ref(zq, zk, v, state_s, state_n, eps=eps)


def rm_attention_prefill_final_state(
    zk: jax.Array, v: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """States after consuming a whole prefix (to switch prefill->decode)."""
    s = jnp.einsum("bhsf,bhsd->bhfd", zk.astype(jnp.float32),
                   v.astype(jnp.float32))
    n = jnp.sum(zk.astype(jnp.float32), axis=2)
    return s, n


# ===========================================================================
# Fused featurize+attention (DESIGN.md §13)
#
# The ops below take RAW (pre-scaled) q/k plus the packed RM layout
# (``w [max_degree, F, d]``, per-column degrees and scales from
# ``core.plan``) instead of pre-featurized Z — featurization happens inside
# the attention kernel's VMEM tiles, so the O(T * F) Z tensors never touch
# HBM. Numerically they match the two-launch composition
# ``rm_attention_*(rm_feature_fused(q), rm_feature_fused(k) * kvalid, v)``
# exactly in structure (same fp32 accumulation order per tile), so parity
# holds at 1e-5.
#
# ``col_deg``/``col_scale`` must be HOST constants (numpy, from
# ``plan.column_degrees()`` / ``plan.column_scales()``): they ride through
# ``jax.custom_vjp`` as static hashable tuples, which sidesteps the
# integer-cotangent (float0) bookkeeping a traced int32 operand would need.
# ===========================================================================
def _static_cols(col_deg, col_scale) -> Tuple[Tuple[int, ...],
                                              Tuple[float, ...]]:
    if isinstance(col_deg, tuple) and isinstance(col_scale, tuple):
        return col_deg, col_scale
    return (tuple(int(x) for x in np.asarray(col_deg)),
            tuple(float(x) for x in np.asarray(col_scale)))


def _featurize_ref4(x, w, deg, scale):
    """Differentiable featurize over [B, H, T, d] via the rm_feature ref."""
    from repro.kernels.rm_feature.ref import rm_feature_fused_ref

    b, h, t, d = x.shape
    z = rm_feature_fused_ref(x.reshape(b * h * t, d), w, deg, scale)
    return z.reshape(b, h, t, -1)


def _fused_causal_jnp(q, k, v, kvalid, w, deg, scale, chunk: int,
                      eps: float):
    """jnp oracle AND backward-pass formulation of the fused causal op."""
    zq = _featurize_ref4(q, w, deg, scale)
    zk = _featurize_ref4(k, w, deg, scale) * kvalid[:, None, :, None]
    return _causal_chunked_jnp(zq, zk, v, chunk, eps)


def _fused_noncausal_jnp(q, k, v, kvalid, w, deg, scale, eps: float):
    zq = _featurize_ref4(q, w, deg, scale)
    zk = _featurize_ref4(k, w, deg, scale) * kvalid[:, None, :, None]
    return rm_attention_noncausal(zq, zk, v, eps=eps)


def _fused_pad(q, k, v, kvalid, w, deg_t, scale_t, chunk, block_f):
    """Pad T to the chunk multiple and F to the feature-block multiple.

    Padded feature columns get degree 0 / scale 0, so their running product
    collapses to ``1 * 0 = 0`` and they contribute nothing to scores or
    state. Padded key rows are zeroed through ``kvalid``.
    """
    b, h, t, d = q.shape
    f = w.shape[1]
    chunk = max(1, min(chunk, _round_up(t, 8)))
    bf = max(1, min(block_f, _round_up(f, 8)))
    tp = _round_up(t, chunk)
    f_pad = _round_up(f, bf)
    q_p, k_p, v_p = _pad_t(q, tp - t), _pad_t(k, tp - t), _pad_t(v, tp - t)
    kval = jnp.pad(kvalid.astype(jnp.float32), ((0, 0), (0, tp - t)))
    kval3 = jnp.broadcast_to(kval[:, None, :], (b, h, tp))
    w_p = jnp.pad(w, ((0, 0), (0, f_pad - f), (0, 0)))
    deg = jnp.asarray(deg_t + (0,) * (f_pad - f), jnp.int32)
    scale = jnp.asarray(scale_t + (0.0,) * (f_pad - f), jnp.float32)
    dv = v.shape[-1]
    return (q_p.reshape(b * h, tp, d), k_p.reshape(b * h, tp, d),
            v_p.reshape(b * h, tp, dv), kval3.reshape(b * h, tp, 1),
            w_p, deg, scale, chunk, bf, tp, f_pad)


def _fused_causal_launch(q, k, v, kvalid, w, deg_t, scale_t, chunk, block_f,
                         eps, interpret):
    """Pallas causal launch; returns (out, s_final, n_final) cropped."""
    b, h, t, _ = q.shape
    dv = v.shape[-1]
    f = w.shape[1]
    (qf, kf, vf, kval3, w_p, deg, scale, chunk, bf, tp,
     f_pad) = _fused_pad(q, k, v, kvalid, w, deg_t, scale_t, chunk, block_f)
    with _kernel_scope("rm_attn_fused"):
        out, s, n = rm_fused_attention_pallas(
            qf, kf, vf, kval3, w_p, deg, scale,
            chunk=chunk, block_f=bf, eps=eps, interpret=interpret)
    return (out.reshape(b, h, tp, dv)[:, :, :t],
            s.reshape(b, h, f_pad, dv)[:, :, :f],
            n.reshape(b, h, f_pad)[:, :, :f])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _fused_causal(q, k, v, kvalid, w, deg_t, scale_t, chunk, block_f, eps,
                  interpret):
    out, _, _ = _fused_causal_launch(q, k, v, kvalid, w, deg_t, scale_t,
                                     chunk, block_f, eps, interpret)
    return out


def _fused_causal_fwd(q, k, v, kvalid, w, deg_t, scale_t, chunk, block_f,
                      eps, interpret):
    out = _fused_causal(q, k, v, kvalid, w, deg_t, scale_t, chunk, block_f,
                        eps, interpret)
    return out, (q, k, v, kvalid, w)


def _fused_causal_bwd(deg_t, scale_t, chunk, block_f, eps, interpret, res,
                      g):
    q, k, v, kvalid, w = res
    deg = jnp.asarray(deg_t, jnp.int32)
    scale = jnp.asarray(scale_t, jnp.float32)
    _, vjp = jax.vjp(
        lambda a, b_, c, kv, ww: _fused_causal_jnp(a, b_, c, kv, ww, deg,
                                                   scale, chunk, eps),
        q, k, v, kvalid, w)
    return vjp(g.astype(jnp.float32))


_fused_causal.defvjp(_fused_causal_fwd, _fused_causal_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _fused_noncausal(q, k, v, kvalid, w, deg_t, scale_t, chunk, block_f,
                     eps, interpret):
    b, h, t, _ = q.shape
    dv = v.shape[-1]
    (qf, kf, vf, kval3, w_p, deg, scale, chunk, bf, tp,
     f_pad) = _fused_pad(q, k, v, kvalid, w, deg_t, scale_t, chunk, block_f)
    with _kernel_scope("rm_attn_fused"):
        s, n = rm_fused_state_pallas(kf, vf, kval3, w_p, deg, scale,
                                     chunk=chunk, block_f=bf,
                                     interpret=interpret)
        out = rm_fused_apply_pallas(qf, s, n, w_p, deg, scale, chunk=chunk,
                                    block_f=bf, eps=eps, interpret=interpret)
    return out.reshape(b, h, tp, dv)[:, :, :t]


def _fused_noncausal_fwd(q, k, v, kvalid, w, deg_t, scale_t, chunk, block_f,
                         eps, interpret):
    out = _fused_noncausal(q, k, v, kvalid, w, deg_t, scale_t, chunk,
                           block_f, eps, interpret)
    return out, (q, k, v, kvalid, w)


def _fused_noncausal_bwd(deg_t, scale_t, chunk, block_f, eps, interpret,
                         res, g):
    q, k, v, kvalid, w = res
    deg = jnp.asarray(deg_t, jnp.int32)
    scale = jnp.asarray(scale_t, jnp.float32)
    _, vjp = jax.vjp(
        lambda a, b_, c, kv, ww: _fused_noncausal_jnp(a, b_, c, kv, ww, deg,
                                                      scale, eps),
        q, k, v, kvalid, w)
    return vjp(g.astype(jnp.float32))


_fused_noncausal.defvjp(_fused_noncausal_fwd, _fused_noncausal_bwd)


def _fused_defaults(q, w, kvalid, chunk, block_f, use_pallas, interpret):
    from repro.kernels.common import default_interpret, get_attention_blocks

    if use_pallas is None:
        use_pallas = not default_interpret()
    if interpret is None:
        interpret = default_interpret()
    if kvalid is None:
        kvalid = jnp.ones((q.shape[0], q.shape[2]), jnp.float32)
    if chunk is None or block_f is None:
        sel_chunk, sel_bf = get_attention_blocks(
            "rm_attn_fused", d=q.shape[-1], depth=w.shape[0],
            t=q.shape[2], f=w.shape[1], dv=0, dtype=q.dtype)
        chunk = sel_chunk if chunk is None else chunk
        block_f = sel_bf if block_f is None else block_f
    return kvalid, chunk, block_f, use_pallas, interpret


def rm_attention_fused_causal(
    q: jax.Array,          # [B, H, T, d]  pre-scaled queries (NOT features)
    k: jax.Array,          # [B, H, T, d]
    v: jax.Array,          # [B, H, T, dv]
    w: jax.Array,          # [max_degree, F, d] packed omegas (pack_omegas)
    col_deg,               # [F] host int array/tuple (plan.column_degrees())
    col_scale,             # [F] host float array/tuple
    *,
    kvalid: Optional[jax.Array] = None,   # [B, T] 1.0 real / 0.0 padded key
    chunk: Optional[int] = 128,
    block_f: Optional[int] = None,
    eps: float = 1e-4,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused causal RM attention: featurize q/k in VMEM, never write Z.

    Equivalent to ``rm_attention_causal(Z(q), Z(k) * kvalid, v)`` with
    ``Z = rm_feature_fused(.., w, col_deg, col_scale)``; differentiable via
    a chunked-XLA custom VJP (the backward featurizes in XLA — training
    still saves the two forward Z round-trips).
    """
    kvalid, chunk, block_f, use_pallas, interpret = _fused_defaults(
        q, w, kvalid, chunk, block_f, use_pallas, interpret)
    deg_t, scale_t = _static_cols(col_deg, col_scale)
    if q.shape[0] * q.shape[1] == 0 or q.shape[2] == 0:
        return jnp.zeros(v.shape, jnp.float32)
    if not use_pallas or w.shape[0] == 0 or w.shape[1] == 0:
        return _fused_causal_jnp(q, k, v, kvalid, w,
                                 jnp.asarray(deg_t, jnp.int32),
                                 jnp.asarray(scale_t, jnp.float32),
                                 chunk, eps)
    return _fused_causal(q, k, v, kvalid, w, deg_t, scale_t, chunk, block_f,
                         eps, interpret)


def rm_attention_fused_noncausal(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    col_deg,
    col_scale,
    *,
    kvalid: Optional[jax.Array] = None,
    chunk: Optional[int] = 128,
    block_f: Optional[int] = None,
    eps: float = 1e-4,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused bidirectional RM attention (state kernel + apply kernel)."""
    kvalid, chunk, block_f, use_pallas, interpret = _fused_defaults(
        q, w, kvalid, chunk, block_f, use_pallas, interpret)
    deg_t, scale_t = _static_cols(col_deg, col_scale)
    if q.shape[0] * q.shape[1] == 0 or q.shape[2] == 0:
        return jnp.zeros(v.shape, jnp.float32)
    if not use_pallas or w.shape[0] == 0 or w.shape[1] == 0:
        return _fused_noncausal_jnp(q, k, v, kvalid, w,
                                    jnp.asarray(deg_t, jnp.int32),
                                    jnp.asarray(scale_t, jnp.float32), eps)
    return _fused_noncausal(q, k, v, kvalid, w, deg_t, scale_t, chunk,
                            block_f, eps, interpret)


def rm_attention_fused_prefill(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    col_deg,
    col_scale,
    *,
    kvalid: Optional[jax.Array] = None,
    chunk: Optional[int] = 128,
    block_f: Optional[int] = None,
    eps: float = 1e-4,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused prefill: causal outputs AND the final decode state (S, n) from
    the SAME launch — the causal kernel's state scratch holds exactly the
    whole-prefix state after the last chunk, so prefill->decode handoff
    costs zero extra HBM passes. Serving-only (no VJP)."""
    kvalid, chunk, block_f, use_pallas, interpret = _fused_defaults(
        q, w, kvalid, chunk, block_f, use_pallas, interpret)
    deg_t, scale_t = _static_cols(col_deg, col_scale)
    b, h, t, _ = q.shape
    f, dv = w.shape[1], v.shape[-1]
    if b * h == 0 or t == 0:
        return (jnp.zeros(v.shape, jnp.float32),
                jnp.zeros((b, h, f, dv), jnp.float32),
                jnp.zeros((b, h, f), jnp.float32))
    if not use_pallas or w.shape[0] == 0 or w.shape[1] == 0:
        deg = jnp.asarray(deg_t, jnp.int32)
        scale = jnp.asarray(scale_t, jnp.float32)
        out = _fused_causal_jnp(q, k, v, kvalid, w, deg, scale, chunk, eps)
        zk = _featurize_ref4(k, w, deg, scale) * kvalid[:, None, :, None]
        s, n = rm_attention_prefill_final_state(zk, v)
        return out, s, n
    return _fused_causal_launch(q, k, v, kvalid, w, deg_t, scale_t, chunk,
                                block_f, eps, interpret)


def rm_attention_fused_decode_step(
    q: jax.Array,        # [B, H, d]  pre-scaled query (NOT features)
    k: jax.Array,        # [B, H, d]
    v: jax.Array,        # [B, H, dv]
    state_s: jax.Array,  # [B, H, F, dv]
    state_n: jax.Array,  # [B, H, F]
    w: jax.Array,        # [max_degree, F, d]
    col_deg,
    col_scale,
    *,
    eps: float = 1e-4,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused decode step: ONE featurize launch for q and k together.

    The two-launch decode path featurizes the new query and key separately
    (two ``rm_feature_fused`` launches per generated token). Stacking them
    along the row axis halves the per-token launch count; the O(1) state
    update itself is two GEMVs and stays in XLA.
    """
    from repro.kernels.common import default_interpret
    from repro.kernels.rm_feature.ops import rm_feature_fused

    if use_pallas is None:
        use_pallas = not default_interpret()
    b, h, d = q.shape
    f = w.shape[1]
    x2 = jnp.concatenate([q.reshape(b * h, d), k.reshape(b * h, d)], axis=0)
    z2 = rm_feature_fused(x2, w, jnp.asarray(col_deg, jnp.int32),
                          jnp.asarray(col_scale, jnp.float32),
                          use_pallas=use_pallas, interpret=interpret)
    zq = z2[:b * h].reshape(b, h, f)
    zk = z2[b * h:].reshape(b, h, f)
    return rm_attention_decode_ref(zq, zk, v, state_s, state_n, eps=eps)
