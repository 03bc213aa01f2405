"""jit'd public wrapper around the fused structured Pallas kernel.

``structured_feature_fused`` applies the whole padded random section of a
``StructuredPlan`` (packed layout, ``repro.structured.plan
.pack_structured``) in one Pallas launch: it pads (batch, stack) to
VMEM-budgeted tiles — feature tiles are whole d_pad-column stacks, so the
generic block ladder's feature width is snapped down to a stack multiple —
and falls back to the pure-jnp mirror
(``repro.structured.ref.structured_feature_fused_ref``) when Pallas is off
or the plan has no random columns.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import common as _kcommon
from repro.kernels.common import get_feature_blocks as _get_blocks
from repro.kernels.common import round_up as _round_up
from repro.kernels.structured_feature.structured_feature import (
    structured_feature_fused_pallas,
)
from repro.obs.trace import kernel_scope as _kernel_scope
from repro.structured.ref import structured_feature_fused_ref


def structured_feature_fused(
    x: jax.Array,          # [..., d_pad] (zero-padded to the Hadamard size)
    d1: jax.Array,         # [max_degree, S, d_pad]  (pack_structured)
    d2: jax.Array,         # [max_degree, S, d_pad]
    col_deg: jax.Array,    # [S * d_pad] int32 per-column product depth
    col_scale: jax.Array,  # [S * d_pad] per-column scale (0 on surplus)
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
    blocks: Optional[tuple] = None,
) -> jax.Array:            # [..., S * d_pad] float32
    """Apply the packed structured buckets: one Pallas launch, every column.

    SPMD-safe (no host callbacks, shape-static tiling): usable inside a
    ``shard_map`` body, where the sharded estimator path runs one launch
    per feature shard over that shard's ``[max_degree, S/shards, d_pad]``
    slice of the packed tensors (tests/dist_scripts/
    run_sharded_estimators.py checks interpret-mode parity under shard_map
    for every registry entry).

    ``x``/``d1``/``d2`` enter the launch in their incoming dtype (bf16
    under the mixed precision policy); the accumulator is fp32.
    """
    if interpret is None:
        interpret = _kcommon.default_interpret()
    batch_shape = x.shape[:-1]
    m = x.shape[-1]
    k, s, _ = d1.shape
    cols = s * m
    xf = x.reshape(-1, m)
    if xf.shape[0] == 0:   # degenerate row chunk: skip the padded launch
        return jnp.zeros((*batch_shape, cols), jnp.float32)
    if not use_pallas or k == 0 or s == 0:
        out = structured_feature_fused_ref(xf, d1, d2, col_deg, col_scale)
        return out.reshape(*batch_shape, cols)

    b = xf.shape[0]
    # TWO packed sign tensors; the fp32 live set per tile is the
    # accumulator plus the WHT intermediate and the output buffer
    bm, bf = blocks or _get_blocks("structured_feature", m, k, b, cols,
                                   dtype=x.dtype, weight_tensors=2,
                                   accumulators=4)
    # feature tiles cover WHOLE stacks (the ladder width snapped down to a
    # multiple of d_pad, never below one stack), and on TPU a tile's
    # bs * d_pad lanes must be a multiple of 128 unless it spans every
    # stack: round the stack count up to that unit, or take all stacks.
    bs = max(1, bf // m)
    unit = max(1, 128 // m)
    bs = s if _round_up(bs, unit) >= s else _round_up(bs, unit)
    bf = bs * m
    with _kernel_scope("structured_feature"):
        b_pad = _round_up(max(b, bm), bm)
        s_pad = _round_up(max(s, bs), bs)
        xp = jnp.pad(xf, ((0, b_pad - b), (0, 0)))
        ps = s_pad - s
        d1p = jnp.pad(d1, ((0, 0), (0, ps), (0, 0)))
        d2p = jnp.pad(d2, ((0, 0), (0, ps), (0, 0)))
        # padding stacks: depth 0 keeps the accumulator at 1; zero scales
        # make every pad column exactly 0 before the slice.
        deg_p = jnp.pad(col_deg.astype(jnp.int32), ((0, ps * m),))
        scale_p = jnp.pad(col_scale.astype(jnp.float32), ((0, ps * m),))
        out = structured_feature_fused_pallas(
            xp, d1p, d2p, deg_p, scale_p,
            block_b=bm, block_s=bs, interpret=interpret,
        )[:b, :cols]
    return out.reshape(*batch_shape, cols)
