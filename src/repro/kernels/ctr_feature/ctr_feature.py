"""Pallas TPU kernel for fused complex-to-real (CTR) feature application.

``ctr_feature_fused_pallas`` applies every complex bucket of a ``CtrPlan``
in ONE launch (DESIGN.md §11): a masked COMPLEX running product over degree
slots — the ``rm_feature_fused`` loop with (real, imag) accumulator pairs,
exactly the stage-1 structure of the TensorSketch kernel —

    (Ar, Ai) <- (Ar Pr - Ai Pi, Ar Pi + Ai Pr),   P_j = x (Wr_j + i Wi_j)^T,

followed by per-column scales on BOTH accumulators, written to two output
tiles (the Re half and the Im half of the CtR feature vector). Every slot
projection is an MXU matmul; the accumulators stay in VMEM.

Unlike TensorSketch there is no cross-column mixing stage (no inverse DFT),
so the grid tiles (batch, complex-feature) like ``rm_feature_fused`` — and
like there, columns are laid out in ascending degree order, so each feature
tile's loop exits at the TILE's max depth, not the global one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ctr_fused_kernel(x_ref, wr_ref, wi_ref, deg_ref, scale_ref,
                      ore_ref, oim_ref):
    # Native-dtype MXU operands (fp32 or bf16 under the precision policy);
    # both accumulators are fp32 VMEM buffers and every dot carries
    # preferred_element_type=float32 — bf16-in / fp32-accum.
    x = x_ref[...]                                # [bm, d]
    deg = deg_ref[...]                            # [1, bf] int32
    bm = x.shape[0]
    bf = deg.shape[-1]

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

    depth = jnp.max(deg)                          # tile-local product depth
    ar, ai = jax.lax.fori_loop(
        0, depth, step,
        (jnp.ones((bm, bf), jnp.float32), jnp.zeros((bm, bf), jnp.float32)),
    )
    scale = scale_ref[...].astype(jnp.float32)
    ore_ref[...] = (ar * scale).astype(ore_ref.dtype)
    oim_ref[...] = (ai * scale).astype(oim_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_f", "interpret")
)
def ctr_feature_fused_pallas(
    x: jax.Array,          # [B, d]               (B pre-padded to block_b)
    wr: jax.Array,         # [max_degree, Fc, d]  (Fc pre-padded to block_f)
    wi: jax.Array,         # [max_degree, Fc, d]
    col_deg: jax.Array,    # [Fc] int32           (padding columns: 0)
    col_scale: jax.Array,  # [Fc] float32         (padding columns: 0)
    *,
    block_b: int = 256,
    block_f: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:   # ([B, Fc], [B, Fc]) float32 (Re, Im)
    """One launch over (batch, complex-feature) tiles; two output tensors.

    Returns the (Re, Im) halves separately — the ops-layer wrapper
    concatenates them into the ``[Re | Im]`` CtR column layout after
    un-padding, keeping the kernel free of cross-half indexing.
    """
    b, d = x.shape
    k, fc, _ = wr.shape
    assert b % block_b == 0 and fc % block_f == 0, (b, fc, block_b, block_f)
    grid = (b // block_b, fc // block_f)
    out_shape = jax.ShapeDtypeStruct((b, fc), jnp.float32)
    return pl.pallas_call(
        _ctr_fused_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((k, block_f, d), lambda i, j: (0, j, 0)),
            pl.BlockSpec((k, block_f, d), lambda i, j: (0, j, 0)),
            pl.BlockSpec((1, block_f), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_f), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, block_f), lambda i, j: (i, j)),
            pl.BlockSpec((block_b, block_f), lambda i, j: (i, j)),
        ],
        out_shape=[out_shape, out_shape],
        interpret=interpret,
    )(x, wr, wi, col_deg.reshape(1, fc), col_scale.reshape(1, fc))
