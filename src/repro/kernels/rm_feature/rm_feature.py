"""Pallas TPU kernels for Random-Maclaurin feature maps.

Two kernels (DESIGN.md §3):

``rm_feature_fused_pallas`` — the whole map in ONE launch. Inputs follow the
``FeaturePlan`` packed layout: ``w [max_degree, F, d]`` holds every column's
product slots (const columns use none, the H0/1 identity block uses slot 0,
degree-n columns use slots 0..n-1), ``col_deg [F]`` is each column's product
depth and ``col_scale [F]`` its final scale. Per (batch, feature) tile the
kernel runs a masked running product

    acc <- 1;  for j < max(col_deg in tile):  acc <- where(j < deg, acc * x W_j^T, acc)

as back-to-back MXU matmuls with the accumulator held in VMEM — one HBM read
of x, one of w, one HBM write of the output tile, no per-bucket relaunch and
no final concatenate. The loop bound is the max depth of the *tile*, not the
global max: columns are laid out in ascending degree order, so low-degree
tiles exit after their own depth (this is where the fused kernel beats the
per-bucket path even on FLOPs).

``rm_feature_bucket_pallas`` — the legacy single-bucket kernel (one launch
per degree). Kept as the comparison baseline for tests and
``benchmarks/rm_feature_bench.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# ---------------------------------------------------------------------------
# fused whole-map kernel
# ---------------------------------------------------------------------------
def _rm_fused_kernel(x_ref, w_ref, deg_ref, scale_ref, o_ref):
    # x/w stay in their STORED dtype (fp32 or bf16 under the bf16 precision
    # policy) — the MXU operands are native, while every dot carries
    # preferred_element_type=float32 and the running product accumulates in
    # an fp32 VMEM buffer. bf16-in / fp32-accum, never bf16 accumulation.
    x = x_ref[...]                                # [bm, d]
    deg = deg_ref[...]                            # [1, bf] int32
    bm = x.shape[0]
    bf = deg.shape[-1]

    def step(j, acc):
        w = w_ref[pl.ds(j, 1), :, :]
        w = w.reshape(w.shape[1], w.shape[2])     # [bf, d]
        pj = jax.lax.dot_general(
            x, w,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                         # [bm, bf]
        return jnp.where(j < deg, acc * pj, acc)

    depth = jnp.max(deg)                          # tile-local product depth
    acc = jax.lax.fori_loop(0, depth, step, jnp.ones((bm, bf), jnp.float32))
    o_ref[...] = (acc * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_f", "interpret")
)
def rm_feature_fused_pallas(
    x: jax.Array,          # [B, d]              (B pre-padded to block_b)
    w: jax.Array,          # [max_degree, F, d]  (F pre-padded to block_f)
    col_deg: jax.Array,    # [F] int32           (padding columns: 0)
    col_scale: jax.Array,  # [F] float32         (padding columns: 0)
    *,
    block_b: int = 256,
    block_f: int = 128,
    interpret: bool = False,
) -> jax.Array:            # [B, F] float32
    b, d = x.shape
    k, f, _ = w.shape
    assert b % block_b == 0 and f % block_f == 0, (b, f, block_b, block_f)
    grid = (b // block_b, f // block_f)
    return pl.pallas_call(
        _rm_fused_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((k, block_f, d), lambda i, j: (0, j, 0)),
            pl.BlockSpec((1, block_f), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_f), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_f), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, f), jnp.float32),
        interpret=interpret,
    )(x, w, col_deg.reshape(1, f), col_scale.reshape(1, f))


# ---------------------------------------------------------------------------
# legacy per-bucket kernel (comparison baseline)
# ---------------------------------------------------------------------------
def _rm_feature_kernel(x_ref, w_ref, o_ref, *, degree: int, scale: float):
    x = x_ref[...]                                # [bm, d] native dtype
    acc = None
    for j in range(degree):
        w = w_ref[j]                              # [bf, d]
        pj = jax.lax.dot_general(
            x, w,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                         # [bm, bf]
        acc = pj if acc is None else acc * pj
    o_ref[...] = (acc * scale).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("degree", "scale", "block_b", "block_f", "interpret"),
)
def rm_feature_bucket_pallas(
    x: jax.Array,        # [B, d]   (B, d already padded by ops.py)
    omega: jax.Array,    # [degree, F, d]
    *,
    degree: int,
    scale: float,
    block_b: int = 256,
    block_f: int = 128,
    interpret: bool = False,
) -> jax.Array:          # [B, F] float32
    b, d = x.shape
    f = omega.shape[1]
    assert b % block_b == 0 and f % block_f == 0, (b, f, block_b, block_f)
    grid = (b // block_b, f // block_f)
    return pl.pallas_call(
        functools.partial(_rm_feature_kernel, degree=degree, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((degree, block_f, d), lambda i, j: (0, j, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_f), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, f), jnp.float32),
        interpret=interpret,
    )(x, omega)
