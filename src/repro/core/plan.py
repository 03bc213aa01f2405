"""FeaturePlan — the single source of truth for RM feature-map plans.

Every Random-Maclaurin path in the repo (SVM featurization via
``RMFeatureMap``, the static per-layer plans inside jitted models, and the
Pallas-accelerated ``repro.kernels.rm_feature`` ops) is driven by one host-side
object built here:

    degree measure  ->  stratified / iid allocation  ->  per-degree scales
                    ->  packed fused layout (DESIGN.md §3)

A ``FeaturePlan`` is a hashable NamedTuple, so it passes through
``jax.jit``/``lax.scan`` as a static constant, and it fully determines the
*column layout* of the feature vector:

    [ h01 const | h01 identity block | degree-0 const | degree buckets asc ]

For the fused kernel, every output column f is expressed uniformly as

    z_f(x) = col_scale[f] * prod_{j < col_degree[f]} <W[j, f, :], x>

with ``W`` a single ``[max_degree, F, d]`` tensor (``pack_omegas``): const
columns have degree 0 (empty product), the H0/1 identity block is degree 1
with one-hot rows, and degree-n bucket columns carry n Rademacher rows. This
lets the WHOLE map run as ONE Pallas launch (a masked running product over
degree slots) instead of one launch per degree bucket.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.maclaurin import DotProductKernel

__all__ = [
    "FeaturePlan",
    "BIAS_TAIL_DEGREES",
    "allocate_features",
    "make_feature_plan",
    "init_omegas",
    "pack_omegas",
    "apply_plan",
    "plan_output_dim",
]

# ``coefs_host`` carries this many Taylor coefficients BEYOND n_max so
# ``truncation_bias`` accounts for the series tail the plan can never
# allocate (paper §4.2's truncation error), not just in-range degrees that
# happened to get zero features. With the window fixed, the reported bias is
# monotonically non-increasing in n_max for decaying-coefficient kernels —
# the conformance contract tests/test_estimator_conformance.py enforces.
BIAS_TAIL_DEGREES = 8


# ---------------------------------------------------------------------------
# plan serialization (shared with SketchPlan — repro.sketch.plan)
# ---------------------------------------------------------------------------
_PLAN_TUPLE_FIELDS = ("degrees", "counts", "scales", "coefs_host")


def plan_to_json(plan) -> str:
    """Any plan NamedTuple -> JSON carrying every field (cross-host repro)."""
    import json

    return json.dumps({f: getattr(plan, f) for f in plan._fields})


def plan_from_json(cls, s: str):
    import json

    d = json.loads(s)
    for f in _PLAN_TUPLE_FIELDS:
        if f in d:
            d[f] = tuple(d[f])
    return cls(**d)


# ---------------------------------------------------------------------------
# allocation (shared by Algorithm 1, static plans, and Algorithm 2)
# ---------------------------------------------------------------------------
def allocate_features(
    coefs: np.ndarray,
    q: np.ndarray,
    num_features: int,
    *,
    stratified: bool,
    seed: int = 0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Split a budget of ``num_features`` across degrees of measure ``q``.

    Returns ``(counts, scales)`` over degrees ``0..len(q)-1``:

    * ``stratified=True`` — deterministic counts ``c_n = round(D q_n)``
      (largest-remainder rounding) with exact weights ``sqrt(a_n / c_n)``;
      no degree-sampling variance, coincides with the paper's §4.2 truncated
      construction under the proportional measure.
    * ``stratified=False`` — paper-faithful Algorithm 1: iid draws ``N ~ q``
      with importance weights ``sqrt(a_n / q_n) / sqrt(D)``; exactly unbiased.
      The draws come from a fresh ``Philox(seed)`` generator each call, so
      identical seeds give identical allocations; ``make_feature_plan``
      records both the seed and the realized counts on the ``FeaturePlan``.

    ``scales[n]`` is 0 where ``counts[n] == 0``.
    """
    if stratified:
        raw = q * num_features
        counts = np.floor(raw).astype(np.int64)
        deficit = num_features - int(counts.sum())
        if deficit > 0:
            order = np.argsort(-(raw - counts))
            counts[order[:deficit]] += 1
    else:
        rng = np.random.Generator(np.random.Philox(seed))
        draws = rng.choice(len(q), size=num_features, p=q)
        counts = np.bincount(draws, minlength=len(q)).astype(np.int64)

    scales = np.zeros(len(q), dtype=np.float64)
    nz = counts > 0
    if stratified:
        scales[nz] = np.sqrt(coefs[nz] / counts[nz])
    else:
        scales[nz] = np.sqrt(coefs[nz] / q[nz]) / np.sqrt(num_features)
    return counts, scales


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------
class FeaturePlan(NamedTuple):
    """Hashable RM feature-map plan: static through jit/scan.

    ``degrees``/``counts``/``scales`` describe the degree >= 1 random buckets
    (ascending). ``const`` is the collapsed degree-0 column value (0.0 when
    absent). The H0/1 variant (paper §6.1) prepends an exact
    ``[sqrt(a_0), sqrt(a_1) x]`` block. ``seed`` records the
    ``allocate_features`` seed alongside the realized allocation (counts), so
    iid-mode plans are reproducible across hosts: the plan's repr and
    ``to_json`` carry everything needed to rebuild identical column layouts.
    """

    degrees: Tuple[int, ...]
    counts: Tuple[int, ...]
    scales: Tuple[float, ...]
    const: float
    h01: bool
    h01_a0: float
    h01_a1: float
    input_dim: int
    num_random: int                   # D, the random-feature budget
    # a_0..a_{n_max + BIAS_TAIL_DEGREES}: allocation sees a_0..a_{n_max};
    # the extra tail window feeds truncation_bias diagnostics only.
    coefs_host: Tuple[float, ...]
    seed: int                         # degree-allocation seed (reproducibility)

    # -- sizes ---------------------------------------------------------------
    @property
    def total_rows(self) -> int:
        """Rademacher rows backing the random buckets: sum_n c_n * n."""
        return int(sum(c * n for c, n in zip(self.counts, self.degrees)))

    @property
    def max_degree(self) -> int:
        """Product depth of the packed layout (0 for a const-only plan)."""
        deg = max(self.degrees) if self.degrees else 0
        if self.h01:
            deg = max(deg, 1)
        return deg

    @property
    def num_prefix_columns(self) -> int:
        """Deterministic columns ahead of the random buckets."""
        pre = 0
        if self.h01:
            pre += 1 + self.input_dim
        if self.const != 0.0:
            pre += 1
        return pre

    @property
    def output_dim(self) -> int:
        return self.num_prefix_columns + int(sum(self.counts))

    # -- fused column layout (host-side, static) -----------------------------
    def column_degrees(self) -> np.ndarray:
        """Per-column product depth, int32 ``[output_dim]``."""
        deg = []
        if self.h01:
            deg.append(0)                      # sqrt(a_0) column
            deg.extend([1] * self.input_dim)   # identity block
        if self.const != 0.0:
            deg.append(0)
        for n, c in zip(self.degrees, self.counts):
            deg.extend([n] * c)
        return np.asarray(deg, dtype=np.int32)

    def column_scales(self) -> np.ndarray:
        """Per-column scale, float32 ``[output_dim]``."""
        sc = []
        if self.h01:
            sc.append(float(np.sqrt(self.h01_a0)))
            sc.extend([float(np.sqrt(self.h01_a1))] * self.input_dim)
        if self.const != 0.0:
            sc.append(float(self.const))
        for s, c in zip(self.scales, self.counts):
            sc.extend([float(s)] * c)
        return np.asarray(sc, dtype=np.float32)

    # -- diagnostics ---------------------------------------------------------
    def truncation_bias(self, radius: float) -> float:
        """Worst-case dropped-degree mass ``sum a_n R^{2n}`` over degrees with
        ``a_n > 0`` but no allocated features (paper §4.2), including the
        ``BIAS_TAIL_DEGREES``-wide coefficient window beyond n_max that the
        plan can never allocate."""
        present = set(self.degrees)
        if self.const != 0.0:
            present.add(0)
        if self.h01:
            present.update((0, 1))
        bias = 0.0
        for n, a_n in enumerate(self.coefs_host):
            if a_n > 0.0 and n not in present:
                bias += a_n * radius ** (2 * n)
        return bias

    # -- serialization -------------------------------------------------------
    def to_json(self) -> str:
        """Full plan state (seed + realized allocation included) as JSON."""
        return plan_to_json(self)

    @classmethod
    def from_json(cls, s: str) -> "FeaturePlan":
        return plan_from_json(cls, s)


def make_feature_plan(
    kernel: DotProductKernel,
    input_dim: int,
    num_features: int,
    *,
    p: float = 2.0,
    measure: str = "geometric",
    h01: bool = False,
    n_max: int = 24,
    radius: float = 1.0,
    stratified: bool = True,
    seed: int = 0,
) -> FeaturePlan:
    """Construct the plan (Algorithm 1 / §6.1 H0/1 / beyond-paper measures).

    This is the ONLY place degree allocation happens; ``make_feature_map``
    (core.feature_map) and ``make_plan_meta`` (core.static_plan) are thin
    wrappers.
    """
    from repro.core.feature_map import degree_measure

    kernel.validate_positive_definite(n_max)
    if h01 and measure == "geometric":
        measure = "geometric_ge2"
    q = degree_measure(kernel, n_max, p=p, kind=measure, radius=radius,
                       min_degree=2 if h01 else 0)
    coefs = kernel.coefs(n_max)
    coefs_diag = kernel.coefs(n_max + BIAS_TAIL_DEGREES)

    counts_all, scales_all = allocate_features(
        coefs, q, num_features, stratified=stratified, seed=seed
    )

    const = 0.0
    if counts_all[0] > 0:
        # c_0 identical constant features collapse into one column of value
        # sqrt(c_0) * scale_0 (same second moment, fewer columns).
        const = float(np.sqrt(counts_all[0]) * scales_all[0])

    degrees, counts, scales = [], [], []
    for n in range(1, n_max + 1):
        if counts_all[n]:
            degrees.append(n)
            counts.append(int(counts_all[n]))
            scales.append(float(scales_all[n]))

    h01_a0 = h01_a1 = 0.0
    if h01:
        h01_a0 = float(kernel.coef(0))
        h01_a1 = float(kernel.coef(1))
        if h01_a0 == 0.0 and h01_a1 == 0.0:
            raise ValueError(
                f"H0/1 is a no-op for kernel {kernel.name}: a_0 = a_1 = 0 "
                "(e.g. homogeneous polynomial kernels — paper §6.2)."
            )

    return FeaturePlan(
        degrees=tuple(degrees),
        counts=tuple(counts),
        scales=tuple(scales),
        const=const,
        h01=h01,
        h01_a0=h01_a0,
        h01_a1=h01_a1,
        input_dim=input_dim,
        num_random=num_features,
        coefs_host=tuple(float(c) for c in coefs_diag),
        seed=seed,
    )


# ---------------------------------------------------------------------------
# parameters and packing
# ---------------------------------------------------------------------------
def init_omegas(plan: FeaturePlan, key: jax.Array, dtype=jnp.float32) -> jax.Array:
    """All Rademacher rows for one plan instance, flat ``[total_rows, d]``.

    Row layout is bucket-major then feature-major: rows
    ``[off_n + i*n, off_n + (i+1)*n)`` belong to feature i of degree bucket n.
    """
    bern = jax.random.bernoulli(key, 0.5, (plan.total_rows, plan.input_dim))
    return (2.0 * bern.astype(dtype) - 1.0).astype(dtype)


def pack_omegas(plan: FeaturePlan, omegas: jax.Array) -> jax.Array:
    """Flat rows ``[total_rows, d]`` -> fused tensor ``[max_degree, F, d]``.

    Column f's product slots are ``W[0:col_degree[f], f, :]``; unused slots
    are zero (they are masked inside the kernel, never multiplied). The H0/1
    identity block occupies slot 0 with one-hot rows; const columns use no
    slots at all. Pure reshape/pad/concat, O(max_degree * F * d) bytes.

    Callers applying one plan repeatedly outside a layer scan should pack
    once and pass ``packed=`` to ``apply_plan``. Inside a scanned layer stack
    the per-layer omegas are scan carries, so the pack re-runs each layer
    step — same traffic the per-bucket path paid in its per-launch
    pad/transpose; storing pre-packed parameters is the remaining headroom.
    """
    d = plan.input_dim
    k = plan.max_degree
    dtype = omegas.dtype
    parts = []
    if plan.h01:
        pre = jnp.zeros((1 + d, k, d), dtype)
        if k > 0:
            eye = jnp.eye(d, dtype=dtype)[:, None, :]          # [d, 1, d]
            pre = pre.at[1:, :1, :].set(eye)
        parts.append(pre)
    if plan.const != 0.0:
        parts.append(jnp.zeros((1, k, d), dtype))
    off = 0
    for n, c in zip(plan.degrees, plan.counts):
        rows = omegas[off : off + c * n].reshape(c, n, d)
        off += c * n
        parts.append(jnp.pad(rows, ((0, 0), (0, k - n), (0, 0))))
    if not parts:
        return jnp.zeros((k, 0, d), dtype)
    packed = jnp.concatenate(parts, axis=0)                     # [F, k, d]
    return jnp.transpose(packed, (1, 0, 2))                     # [k, F, d]


# ---------------------------------------------------------------------------
# application — ONE fused launch (or its jnp mirror)
# ---------------------------------------------------------------------------
def _apply_plan_flat(
    plan: FeaturePlan, omegas: jax.Array, xf: jax.Array, compute_dtype,
    accum_dtype
) -> jax.Array:
    """jnp parity path: one flat ``x @ omegas.T`` + segmented products.

    Emits the exact fused column order (h01 const, identity block, const,
    buckets ascending) without materializing the ``[max_degree, F]`` masked
    product — XLA-friendly and does only ``sum c_n n`` projection columns.

    Mirrors the Pallas precision contract: the projection operands are cast
    to ``compute_dtype`` (bf16 under the mixed policy) while the dot itself
    carries ``preferred_element_type=accum_dtype`` and the segmented
    products run in ``accum_dtype`` — fp32 accumulation either way.
    """
    xc = xf.astype(compute_dtype)
    feats = []
    if plan.h01:
        feats.append(jnp.full((xf.shape[0], 1), np.sqrt(plan.h01_a0),
                              dtype=accum_dtype))
        feats.append(jnp.asarray(np.sqrt(plan.h01_a1), accum_dtype)
                     * xc.astype(accum_dtype))
    if plan.const != 0.0:
        feats.append(jnp.full((xf.shape[0], 1), plan.const, dtype=accum_dtype))
    if plan.total_rows:
        proj = jax.lax.dot_general(
            xc, omegas.astype(compute_dtype),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=accum_dtype,
        )                                               # [B, total_rows]
        off = 0
        for deg, cnt, scale in zip(plan.degrees, plan.counts, plan.scales):
            rows = cnt * deg
            block = proj[:, off : off + rows].reshape(-1, cnt, deg)
            feats.append(jnp.prod(block, axis=-1) * jnp.asarray(scale,
                                                                accum_dtype))
            off += rows
    return jnp.concatenate(feats, axis=-1)


def apply_plan(
    plan: FeaturePlan,
    omegas: jax.Array,
    x: jax.Array,
    accum_dtype=jnp.float32,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
    packed: Optional[jax.Array] = None,
    precision=None,
) -> jax.Array:
    """Featurize ``x [..., d] -> [..., plan.output_dim]``.

    The whole map — const column, H0/1 block, every degree bucket — is a
    single fused application (``repro.kernels.rm_feature.rm_feature_fused``):
    one Pallas launch on TPU, a flat matmul + segmented products on the jnp
    path. ``use_pallas`` defaults to the backend (True on TPU). ``packed``
    short-circuits ``pack_omegas`` for callers that cache the packed tensor.

    ``precision`` (``None``/``"fp32"``/``"bf16"`` or a
    ``repro.common.dtypes.Precision``) selects the INPUT dtype policy: under
    ``"bf16"`` x and the packed omega tensor enter the kernel in bf16 (the
    Rademacher values +-1 are exact in bf16, so only x is rounded) while
    accumulation stays fp32 on both paths.
    """
    # Lazy import: core.plan is imported by kernels-level code at call sites.
    from repro.common.dtypes import resolve_precision
    from repro.kernels.rm_feature.ops import rm_feature_fused

    if x.shape[-1] != plan.input_dim:
        raise ValueError(
            f"expected trailing dim {plan.input_dim}, got {x.shape}"
        )
    if use_pallas is None:
        from repro.kernels.common import default_interpret

        use_pallas = not default_interpret()
    prec = resolve_precision(precision)
    compute_dtype = prec.compute_dtype
    batch_shape = x.shape[:-1]
    xf = x.reshape(-1, plan.input_dim)
    if use_pallas:
        w = (packed if packed is not None
             else pack_omegas(plan, omegas)).astype(compute_dtype)
        col_deg = jnp.asarray(plan.column_degrees())
        col_scale = jnp.asarray(plan.column_scales())
        z = rm_feature_fused(
            xf.astype(compute_dtype), w, col_deg, col_scale,
            use_pallas=True, interpret=interpret,
        )
        z = z.astype(accum_dtype)
    else:
        z = _apply_plan_flat(plan, omegas, xf.astype(accum_dtype),
                             compute_dtype, accum_dtype)
    return z.reshape(*batch_shape, z.shape[-1])


def plan_output_dim(plan: FeaturePlan) -> int:
    """Real output columns of ``apply_plan`` for this plan (prefix columns
    plus one column per allocated random feature)."""
    return plan.output_dim
