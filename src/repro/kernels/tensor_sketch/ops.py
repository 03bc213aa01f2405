"""jit'd public wrapper around the fused TensorSketch Pallas kernel.

``tensor_sketch_fused`` applies the whole sketch-block section of a
``SketchPlan`` (packed frequency-domain layout, ``repro.sketch.plan
.pack_sketch``) in one Pallas launch: it pads the batch to a VMEM-budgeted
tile and the feature axis to lane alignment, and falls back to the pure-jnp
mirror (``repro.sketch.ref.tensor_sketch_fused_ref``) when Pallas is off or
the plan has no sketch blocks.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import common as _kcommon
from repro.kernels.common import get_batch_block as _get_batch_block
from repro.kernels.common import round_up as _round_up
from repro.obs.trace import kernel_scope as _kernel_scope
from repro.sketch.ref import tensor_sketch_fused_ref
from repro.kernels.tensor_sketch.tensor_sketch import tensor_sketch_fused_pallas


def tensor_sketch_fused(
    x: jax.Array,          # [..., d]
    wr: jax.Array,         # [max_degree, Fs, d]   (pack_sketch)
    wi: jax.Array,         # [max_degree, Fs, d]
    col_deg: jax.Array,    # [Fs] int32 per-column product depth
    mr: jax.Array,         # [Fs, Fs] block-diag inverse-DFT, real
    mi: jax.Array,         # [Fs, Fs] block-diag inverse-DFT, imag
    col_scale: jax.Array,  # [Fs] per-column scale
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
    blocks: Optional[tuple] = None,
) -> jax.Array:            # [..., Fs] float32
    """Apply the packed sketch blocks: one Pallas launch for every column.

    SPMD-safe (no host callbacks, shape-static tiling): usable inside a
    ``shard_map`` body, where the sharded estimator path runs one launch
    per feature shard over that shard's degree blocks. Note the 128-lane
    feature pad is a per-LAUNCH cost, so very thin shards (Fs << 128) pay
    proportionally more padding than a single-device launch would.

    ``x``/``wr``/``wi``/``mr``/``mi`` enter the launch in their incoming
    dtype (bf16 under the mixed precision policy — the stage-2 inverse-DFT
    is upcast to fp32 inside the kernel); accumulation is always fp32.
    """
    if interpret is None:
        interpret = _kcommon.default_interpret()
    batch_shape = x.shape[:-1]
    d = x.shape[-1]
    k, fs, _ = wr.shape
    xf = x.reshape(-1, d)
    if xf.shape[0] == 0:   # degenerate row chunk: skip the padded launch
        return jnp.zeros((*batch_shape, fs), jnp.float32)
    if not use_pallas or k == 0 or fs == 0:
        out = tensor_sketch_fused_ref(xf, wr, wi, col_deg, mr, mi, col_scale)
        return out.reshape(*batch_shape, fs)

    b = xf.shape[0]
    f_pad = _round_up(max(fs, 128), 128)
    # budget at the PADDED feature count; blocks=(block_b, _) overrides the
    # cached/heuristic batch tile (the autotuner hook — feature axis stays
    # fully resident in this kernel, so only the batch tile is tunable).
    if blocks is not None:
        bm = int(blocks[0])
    else:
        bm = _get_batch_block("tensor_sketch", d, k, f_pad, b, dtype=x.dtype)
    with _kernel_scope("tensor_sketch"):
        b_pad = _round_up(max(b, bm), bm)
        xp = jnp.pad(xf, ((0, b_pad - b), (0, 0)))
        pf = f_pad - fs
        wrp = jnp.pad(wr, ((0, 0), (0, pf), (0, 0)))
        wip = jnp.pad(wi, ((0, 0), (0, pf), (0, 0)))
        # padded columns: depth 0 keeps the accumulator at (1, 0); zero
        # inverse-DFT rows and zero scales make their outputs exactly 0
        # before the slice.
        deg_p = jnp.pad(col_deg.astype(jnp.int32), ((0, pf),))
        mrp = jnp.pad(mr, ((0, pf), (0, pf)))
        mip = jnp.pad(mi, ((0, pf), (0, pf)))
        scale_p = jnp.pad(col_scale.astype(jnp.float32), ((0, pf),))
        out = tensor_sketch_fused_pallas(
            xp, wrp, wip, deg_p, mrp, mip, scale_p,
            block_b=bm, interpret=interpret,
        )
    return out[:b, :fs].reshape(*batch_shape, fs)
