"""jit'd public wrapper around the fused CTR Pallas kernel.

``ctr_feature_fused`` applies the whole complex-bucket section of a
``CtrPlan`` (packed layout, ``repro.ctr.plan.pack_ctr``) in one Pallas
launch: it pads (batch, complex-feature) to MXU-aligned tiles, picks
VMEM-budgeted block sizes, and falls back to the pure-jnp mirror
(``repro.ctr.ref.ctr_feature_fused_ref``) when Pallas is off or the plan
has no complex columns.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.ctr.ref import ctr_feature_fused_ref
from repro.kernels import common as _kcommon
from repro.kernels.common import get_feature_blocks as _get_blocks
from repro.kernels.common import round_up as _round_up
from repro.obs.trace import kernel_scope as _kernel_scope
from repro.kernels.ctr_feature.ctr_feature import ctr_feature_fused_pallas


def ctr_feature_fused(
    x: jax.Array,          # [..., d]
    wr: jax.Array,         # [max_degree, Fc, d]  (pack_ctr)
    wi: jax.Array,         # [max_degree, Fc, d]
    col_deg: jax.Array,    # [Fc] int32 per-column product depth
    col_scale: jax.Array,  # [Fc] per-complex-column scale
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
    blocks: Optional[tuple] = None,
) -> jax.Array:            # [..., 2 * Fc] float32, layout [Re | Im]
    """Apply the packed complex buckets: one Pallas launch for every column.

    SPMD-safe (no host callbacks, shape-static tiling): usable inside a
    ``shard_map`` body, where the sharded estimator path runs one launch per
    feature shard over that shard's ``[max_degree, Fc/S, d]`` slice of the
    packed tensors (tests/dist_scripts/run_sharded_estimators.py checks
    interpret-mode parity under shard_map for every registry entry).

    ``x``/``wr``/``wi`` enter the launch in their incoming dtype (bf16
    under the mixed precision policy); both accumulators are fp32.
    """
    if interpret is None:
        interpret = _kcommon.default_interpret()
    batch_shape = x.shape[:-1]
    d = x.shape[-1]
    k, fc, _ = wr.shape
    xf = x.reshape(-1, d)
    if xf.shape[0] == 0:   # degenerate row chunk: skip the padded launch
        return jnp.zeros((*batch_shape, 2 * fc), jnp.float32)
    if not use_pallas or k == 0 or fc == 0:
        out = ctr_feature_fused_ref(xf, wr, wi, col_deg, col_scale)
        return out.reshape(*batch_shape, 2 * fc)

    b = xf.shape[0]
    # TWO packed weight tensors and four [bm, bf] live buffers (complex
    # accumulator pair + both output halves)
    bm, bf = blocks or _get_blocks("ctr_feature", d, k, b, fc, dtype=x.dtype,
                                   weight_tensors=2, accumulators=4)
    with _kernel_scope("ctr_feature"):
        b_pad = _round_up(max(b, bm), bm)
        f_pad = _round_up(max(fc, bf), bf)
        xp = jnp.pad(xf, ((0, b_pad - b), (0, 0)))
        pf = f_pad - fc
        wrp = jnp.pad(wr, ((0, 0), (0, pf), (0, 0)))
        wip = jnp.pad(wi, ((0, 0), (0, pf), (0, 0)))
        # padded columns: depth 0 keeps the accumulator at (1, 0); zero
        # scales make both halves exactly 0 before the slice.
        deg_p = jnp.pad(col_deg.astype(jnp.int32), ((0, pf),))
        scale_p = jnp.pad(col_scale.astype(jnp.float32), ((0, pf),))
        re, im = ctr_feature_fused_pallas(
            xp, wrp, wip, deg_p, scale_p,
            block_b=bm, block_f=bf, interpret=interpret,
        )
        out = jnp.concatenate([re[:b, :fc], im[:b, :fc]], axis=-1)
    return out.reshape(*batch_shape, 2 * fc)
