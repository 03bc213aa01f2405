"""jit'd public wrappers around the rm_feature Pallas kernels.

``rm_feature_fused`` applies a WHOLE feature map (FeaturePlan packed layout)
in one Pallas launch: it pads (batch, feature) to MXU-aligned tiles, picks
VMEM-budgeted block sizes, and falls back to the pure-jnp oracle when Pallas
is off or the plan is degenerate (no product columns). ``rm_feature_bucket``
is the legacy per-degree launch, kept as the benchmark baseline.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.rm_feature.ref import (
    rm_feature_bucket_ref,
    rm_feature_fused_ref,
)
from repro.kernels.rm_feature.rm_feature import (
    rm_feature_bucket_pallas,
    rm_feature_fused_pallas,
)

from repro.kernels import common as _kcommon
from repro.kernels.common import get_feature_blocks as _get_blocks
from repro.kernels.common import round_up as _round_up
from repro.obs.trace import kernel_scope as _kernel_scope


# ---------------------------------------------------------------------------
# fused whole-map application — ONE launch
# ---------------------------------------------------------------------------
def rm_feature_fused(
    x: jax.Array,          # [..., d]
    w: jax.Array,          # [max_degree, F, d] packed (core.plan.pack_omegas)
    col_deg: jax.Array,    # [F] int32 per-column product depth
    col_scale: jax.Array,  # [F] per-column scale
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
    blocks: Optional[tuple] = None,
) -> jax.Array:            # [..., F] float32
    """Apply a packed feature map: one Pallas launch for every column.

    ``blocks=(block_b, block_f)`` overrides the cached/heuristic tile
    choice — the measured ladder autotuner (kernels.common) drives real
    launches through this hook.

    SPMD-safe: no host callbacks and shape-static tiling, so the launch can
    sit inside a ``shard_map`` body — the sharded estimator path
    (repro.distributed.estimator) runs one launch per feature shard with the
    shard's ``[max_degree, F/S, d]`` slice of the packed tensor
    (tests/dist_scripts/run_sharded_estimators.py checks interpret-mode
    parity under shard_map).

    ``x``/``w`` enter the launch in their incoming dtype — the precision
    policy (repro.common.dtypes.Precision) casts them to bf16 upstream for
    the mixed path; accumulation inside the kernel is always fp32 and the
    output is fp32.
    """
    if interpret is None:
        interpret = _kcommon.default_interpret()
    batch_shape = x.shape[:-1]
    d = x.shape[-1]
    k, f, _ = w.shape
    xf = x.reshape(-1, d)
    if xf.shape[0] == 0:   # degenerate row chunk: skip the padded launch
        return jnp.zeros((*batch_shape, f), jnp.float32)
    if not use_pallas or k == 0 or f == 0:
        out = rm_feature_fused_ref(xf, w, col_deg, col_scale)
        return out.reshape(*batch_shape, f)

    b = xf.shape[0]
    bm, bf = blocks or _get_blocks("rm_feature", d, k, b, f, dtype=x.dtype)
    with _kernel_scope("rm_feature"):
        b_pad = _round_up(max(b, bm), bm)
        f_pad = _round_up(max(f, bf), bf)
        xp = jnp.pad(xf, ((0, b_pad - b), (0, 0)))
        wp = jnp.pad(w, ((0, 0), (0, f_pad - f), (0, 0)))
        deg_p = jnp.pad(col_deg.astype(jnp.int32), ((0, f_pad - f),))
        scale_p = jnp.pad(col_scale.astype(jnp.float32), ((0, f_pad - f),))
        out = rm_feature_fused_pallas(
            xp, wp, deg_p, scale_p, block_b=bm, block_f=bf,
            interpret=interpret,
        )
    return out[:b, :f].reshape(*batch_shape, f)


def apply_feature_map(
    fmap,
    x: jax.Array,
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
    precision=None,
) -> jax.Array:
    """Pallas-accelerated equivalent of ``RMFeatureMap.__call__``.

    Thin wrapper over the fused path: identical feature layout (h01 block,
    const column, degree buckets ascending) in ONE launch, so downstream code
    can swap paths freely. ``precision`` selects the feature-kernel input
    dtype policy (``"fp32"`` / ``"bf16"`` — see repro.common.dtypes).
    """
    from repro.core.plan import apply_plan

    return apply_plan(
        fmap.plan, fmap.omegas, x, use_pallas=use_pallas, interpret=interpret,
        precision=precision,
    )


# ---------------------------------------------------------------------------
# legacy per-bucket path (benchmark baseline / kernel tests)
# ---------------------------------------------------------------------------
def rm_feature_bucket(
    x: jax.Array,
    omega: jax.Array,
    degree: int,
    scale: float,
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Apply one degree bucket: x [.., d], omega [count*degree, d] -> [.., count]."""
    if interpret is None:
        interpret = _kcommon.default_interpret()
    batch_shape = x.shape[:-1]
    d = x.shape[-1]
    count = omega.shape[0] // degree
    if not use_pallas or degree < 1:
        out = rm_feature_bucket_ref(x.reshape(-1, d), omega, degree, scale)
        return out.reshape(*batch_shape, count)

    xf = x.reshape(-1, d)
    b = xf.shape[0]
    bm, bf = _get_blocks("rm_feature", d, degree, b, count, dtype=x.dtype)
    b_pad = _round_up(max(b, bm), bm)
    f_pad = _round_up(max(count, bf), bf)
    xp = jnp.pad(xf, ((0, b_pad - b), (0, 0)))
    # omega rows are feature-major: [count, degree, d] -> pad count -> [degree, F, d]
    w = omega.reshape(count, degree, d)
    w = jnp.pad(w, ((0, f_pad - count), (0, 0), (0, 0)))
    w = jnp.transpose(w, (1, 0, 2))  # [degree, F, d]
    out = rm_feature_bucket_pallas(
        xp, w, degree=degree, scale=float(scale), block_b=bm, block_f=bf,
        interpret=interpret,
    )
    return out[:b, :count].reshape(*batch_shape, count)


def apply_feature_map_bucketed(
    fmap,
    x: jax.Array,
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """The pre-fusion path: one launch PER degree bucket plus a concatenate.

    Kept only as the comparison baseline for parity tests and
    ``benchmarks/rm_feature_bench.py``; production paths use
    ``apply_feature_map`` / ``core.plan.apply_plan``.
    """
    plan = fmap.plan
    batch_shape = x.shape[:-1]
    xf = x.reshape(-1, plan.input_dim)
    feats = []
    if plan.h01:
        feats.append(jnp.full((xf.shape[0], 1), np.sqrt(plan.h01_a0),
                              dtype=jnp.float32))
        feats.append(np.sqrt(plan.h01_a1) * xf.astype(jnp.float32))
    if plan.const != 0.0:
        feats.append(jnp.full((xf.shape[0], 1), plan.const, dtype=jnp.float32))
    for deg, scale, omega in zip(plan.degrees, plan.scales,
                                 fmap.bucket_omegas()):
        feats.append(
            rm_feature_bucket(
                xf, omega, deg, float(scale),
                use_pallas=use_pallas, interpret=interpret,
            )
        )
    z = jnp.concatenate(feats, axis=-1)
    return z.reshape(*batch_shape, z.shape[-1])
