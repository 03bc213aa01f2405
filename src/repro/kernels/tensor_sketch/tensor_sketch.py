"""Pallas TPU kernel for fused TensorSketch application.

``tensor_sketch_fused_pallas`` applies every sketch block of a ``SketchPlan``
in ONE launch, using the frequency-domain formulation of DESIGN.md §9: the
FFT of a CountSketch is a dense complex projection of x (FFT is linear), so

    stage 1: masked complex running product over degree slots
             (Ar, Ai) <- (Ar Pr - Ai Pi, Ar Pi + Ai Pr),  P_j = x W_j^T,
             exactly the ``rm_feature_fused`` loop with two accumulators;
    stage 2: one block-diagonal inverse-DFT matmul
             z = Ar Mr^T - Ai Mi^T   (the real part of the circular
             convolution of the CountSketches), then per-column scales.

Both stages are MXU matmuls; the accumulators and the [Fs, Fs] inverse-DFT
stay in VMEM. The grid tiles the BATCH dimension only: stage 2 mixes all
frequencies of a block, and blocks are packed contiguously, so the whole
feature axis stays resident per tile (ops.py budgets the batch tile so the
working set — x, wr/wi, mr/mi, three [bm, Fs] accumulators — fits VMEM).

Like ``rm_feature_fused``, the product loop bound is the max depth over the
resident columns, so low-degree plans exit early.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ts_fused_kernel(x_ref, wr_ref, wi_ref, deg_ref, mr_ref, mi_ref,
                     scale_ref, o_ref):
    # Stage-1 MXU operands stay in their STORED dtype (fp32, or bf16 under
    # the precision policy — halving the HBM read of x and both packed
    # weight tensors); the complex accumulator pair is fp32 VMEM and every
    # dot carries preferred_element_type=float32. Stage 2 runs in fp32 (the
    # accumulators already are; mr/mi are upcast after their bf16 HBM read).
    x = x_ref[...]                                # [bm, d]
    deg = deg_ref[...]                            # [1, Fs] int32
    bm = x.shape[0]
    fs = deg.shape[-1]

    def step(j, carry):
        ar, ai = carry
        wr = wr_ref[pl.ds(j, 1), :, :]
        wr = wr.reshape(wr.shape[1], wr.shape[2])
        wi = wi_ref[pl.ds(j, 1), :, :]
        wi = wi.reshape(wi.shape[1], wi.shape[2])
        dims = (((1,), (1,)), ((), ()))
        pr = jax.lax.dot_general(x, wr, dimension_numbers=dims,
                                 preferred_element_type=jnp.float32)
        pi = jax.lax.dot_general(x, wi, dimension_numbers=dims,
                                 preferred_element_type=jnp.float32)
        nr = ar * pr - ai * pi
        ni = ar * pi + ai * pr
        keep = j < deg
        return jnp.where(keep, nr, ar), jnp.where(keep, ni, ai)

    depth = jnp.max(deg)                          # resident product depth
    ar, ai = jax.lax.fori_loop(
        0, depth, step,
        (jnp.ones((bm, fs), jnp.float32), jnp.zeros((bm, fs), jnp.float32)),
    )
    mr = mr_ref[...].astype(jnp.float32)          # [Fs, Fs]
    mi = mi_ref[...].astype(jnp.float32)
    dims = (((1,), (1,)), ((), ()))
    z = (jax.lax.dot_general(ar, mr, dimension_numbers=dims,
                             preferred_element_type=jnp.float32)
         - jax.lax.dot_general(ai, mi, dimension_numbers=dims,
                               preferred_element_type=jnp.float32))
    o_ref[...] = (z * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def tensor_sketch_fused_pallas(
    x: jax.Array,          # [B, d]               (B pre-padded to block_b)
    wr: jax.Array,         # [max_degree, Fs, d]  (Fs pre-padded, lane-aligned)
    wi: jax.Array,         # [max_degree, Fs, d]
    col_deg: jax.Array,    # [Fs] int32           (padding columns: 0)
    mr: jax.Array,         # [Fs, Fs]             (padding rows/cols: 0)
    mi: jax.Array,         # [Fs, Fs]
    col_scale: jax.Array,  # [Fs] float32         (padding columns: 0)
    *,
    block_b: int = 256,
    interpret: bool = False,
) -> jax.Array:            # [B, Fs] float32
    b, d = x.shape
    k, fs, _ = wr.shape
    assert b % block_b == 0, (b, block_b)
    grid = (b // block_b,)
    return pl.pallas_call(
        _ts_fused_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((k, fs, d), lambda i: (0, 0, 0)),
            pl.BlockSpec((k, fs, d), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, fs), lambda i: (0, 0)),
            pl.BlockSpec((fs, fs), lambda i: (0, 0)),
            pl.BlockSpec((fs, fs), lambda i: (0, 0)),
            pl.BlockSpec((1, fs), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, fs), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, fs), jnp.float32),
        interpret=interpret,
    )(x, wr, wi, col_deg.reshape(1, fs), mr, mi, col_scale.reshape(1, fs))
