"""CtrPlan — complex-to-real (CtR) improved random features.

Wacker, Kanagawa & Filippone, *Improved Random Features for Dot Product
Kernels* (2022), replace the paper's real Rademacher draws with COMPLEX
Rademacher entries ``w_i ~ Uniform{1, i, -1, -i}``. The degree-n product
feature

    z(x) = prod_{j < n} <w_j, x>,      E[ z(x) conj(z(y)) ] = <x, y>^n

stays unbiased (``E[w_i conj(w_k)] = delta_ik``), but the extra phase kills
the self-pairing terms real Rademacher pays: ``E[w_i^2] = 0``, so the
per-degree second moment changes from ``R^n`` with ``R = |x|^2|y|^2 + 2t^2
- 2s`` to ``(B1^n + B2^n)/2`` with ``B1 = |x|^2|y|^2 + t^2 - s``,
``B2 = 2t^2 - s`` (``t = <x,y>``, ``s = sum x_i^2 y_i^2``). Since
``B1 + B2 = R + t^2`` exactly and ``B2 <= B1 <= R`` whenever ``s <= t^2``,
majorization gives the matched-budget win ``B1^n + B2^n <= R^n + t^{2n}``
for every degree n >= 2 on such pairs (a tie at n = 1) — the
aligned/high-kernel-value pairs that dominate Gram error. It is NOT a
pointwise guarantee: mixed-sign near-orthogonal pairs with ``s > t^2`` can
favor real Rademacher. The measured net effect is what the deterministic
test pins: lowest Gram MSE of the three families on the exponential kernel
at matched F. See DESIGN.md §11.

The **complex-to-real** trick makes the estimator a real feature map: stack

    z_R(x) = [ Re z(x) | Im z(x) ],
    <z_R(x), z_R(y)> = Re( z(x) conj(z(y)) ),

so one complex feature yields TWO real columns whose plain real inner
product is the unbiased kernel estimate — downstream consumers (linear
models, linear attention, Gram estimation, feature-axis sharding) never see
a complex dtype. At a matched REAL budget F, CTR draws F/2 complex features
where RM draws F real ones and wins on variance wherever degree >= 2 mass
exists.

This module mirrors ``repro.core.plan`` / ``repro.sketch.plan`` exactly:

    degree measure  ->  complex-feature allocation  ->  sqrt(a_n / c_n)
                    ->  packed fused layout (two real tensors, DESIGN.md §11)

A ``CtrPlan`` is a hashable NamedTuple (jit-static). Column layout:

    [ h01 const | h01 identity block | degree-0 const
      | Re of complex columns, buckets ascending
      | Im of complex columns, buckets ascending ]

Degree 0 (and the H0/1 prefix) are exact real columns computed outside the
kernel, exactly as in the sketch subsystem; only degrees >= 1 draw complex
randomness.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.maclaurin import DotProductKernel
from repro.core.plan import BIAS_TAIL_DEGREES, allocate_features

__all__ = [
    "CtrPlan",
    "make_ctr_plan",
    "init_ctr_params",
    "pack_ctr",
    "apply_ctr_plan",
]


class CtrPlan(NamedTuple):
    """Hashable complex-to-real feature-map plan: static through jit/scan.

    ``degrees``/``counts``/``scales`` describe the degree >= 1 COMPLEX
    feature buckets (ascending): bucket n holds ``counts[i]`` complex
    features of per-feature scale ``scales[i]`` — each contributing one Re
    and one Im real output column at that same scale. ``seed`` records the
    ``allocate_features`` seed so plans reproduce across hosts (``to_json``
    carries every field).
    """

    degrees: Tuple[int, ...]
    counts: Tuple[int, ...]           # complex features per degree bucket
    scales: Tuple[float, ...]         # per-complex-feature scale
    const: float                      # exact degree-0 column (0.0 when absent)
    h01: bool
    h01_a0: float
    h01_a1: float
    input_dim: int
    num_random: int                   # F, the REAL feature budget
    # a_0..a_{n_max + BIAS_TAIL_DEGREES} (tail window: bias diagnostics only)
    coefs_host: Tuple[float, ...]
    seed: int                         # allocation seed (reproducibility)

    # -- sizes ---------------------------------------------------------------
    @property
    def total_rows(self) -> int:
        """Complex Rademacher rows backing the buckets: ``sum_n c_n * n``."""
        return int(sum(c * n for c, n in zip(self.counts, self.degrees)))

    @property
    def max_degree(self) -> int:
        """Product depth of the packed layout (0 for a const-only plan)."""
        return max(self.degrees) if self.degrees else 0

    @property
    def num_complex(self) -> int:
        """Complex features across all buckets (each emits 2 real columns)."""
        return int(sum(self.counts))

    @property
    def num_prefix_columns(self) -> int:
        """Deterministic (exact, zero-variance) columns ahead of the
        random section."""
        pre = 0
        if self.h01:
            pre += 1 + self.input_dim
        if self.const != 0.0:
            pre += 1
        return pre

    @property
    def output_dim(self) -> int:
        """Real output columns: prefix + Re half + Im half."""
        return self.num_prefix_columns + 2 * self.num_complex

    # -- fused column layout (host-side, static; complex section only) -------
    def column_degrees(self) -> np.ndarray:
        """Per COMPLEX column product depth, int32 ``[num_complex]``."""
        deg = []
        for n, c in zip(self.degrees, self.counts):
            deg.extend([n] * c)
        return np.asarray(deg, dtype=np.int32)

    def column_scales(self) -> np.ndarray:
        """Per COMPLEX column scale, float32 ``[num_complex]``.

        The same scale multiplies both the Re and the Im real output column
        of that complex feature.
        """
        sc = []
        for s, c in zip(self.scales, self.counts):
            sc.extend([float(s)] * c)
        return np.asarray(sc, dtype=np.float32)

    # -- diagnostics ---------------------------------------------------------
    def truncation_bias(self, radius: float) -> float:
        """Worst-case dropped-degree mass ``sum a_n R^{2n}`` (paper §4.2),
        tail window beyond n_max included (see core.plan.BIAS_TAIL_DEGREES)."""
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

    # -- serialization (shared body with FeaturePlan/SketchPlan) -------------
    def to_json(self) -> str:
        """Full plan state (seed + realized allocation included) as JSON."""
        from repro.core.plan import plan_to_json

        return plan_to_json(self)

    @classmethod
    def from_json(cls, s: str) -> "CtrPlan":
        """Inverse of ``to_json`` (lossless: conformance-tested)."""
        from repro.core.plan import plan_from_json

        return plan_from_json(cls, s)


def make_ctr_plan(
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
) -> CtrPlan:
    """Allocate complex features across degrees of the Maclaurin measure.

    Args mirror ``core.plan.make_feature_plan`` (the estimator-registry
    ``make_plan`` signature). ``num_features`` is the REAL output budget F:
    after reserving the exact prefix columns (degree-0 const, or the H0/1
    block when ``h01``), the remaining budget funds ``(F - prefix) // 2``
    complex features, each worth two real columns.

    The SAME degree-measure machinery as RM/TensorSketch splits that complex
    budget (``core.feature_map.degree_measure`` over degrees >= 1 — degree 0
    is always exact here, as in the sketch family). Both allocation modes are
    supported: ``stratified=True`` gives deterministic largest-remainder
    counts with exact scales ``sqrt(a_n / c_n)``; ``stratified=False`` is the
    paper-faithful iid draw with importance weights ``sqrt(a_n / q_n) /
    sqrt(D_c)`` (seeded by ``seed``, recorded on the plan).

    Returns the hashable ``CtrPlan``.
    """
    from repro.core.feature_map import degree_measure

    kernel.validate_positive_definite(n_max)
    if h01 and measure == "geometric":
        measure = "geometric_ge2"
    a0 = float(kernel.coef(0))
    a1 = float(kernel.coef(1))
    if h01 and a0 == 0.0 and a1 == 0.0:
        raise ValueError(
            f"H0/1 is a no-op for kernel {kernel.name}: a_0 = a_1 = 0 "
            "(e.g. homogeneous polynomial kernels — paper §6.2)."
        )
    min_degree = 2 if h01 else 1
    q = degree_measure(kernel, n_max, p=p, kind=measure, radius=radius,
                       min_degree=min_degree)
    coefs = kernel.coefs(n_max)
    coefs_diag = kernel.coefs(n_max + BIAS_TAIL_DEGREES)

    prefix = (1 + input_dim) if h01 else (1 if a0 > 0.0 else 0)
    budget = max((num_features - prefix) // 2, 0)
    counts_all, scales_all = allocate_features(
        coefs, q, budget, stratified=stratified, seed=seed
    )

    degrees, counts, scales = [], [], []
    for n in range(min_degree, n_max + 1):
        c = int(counts_all[n])
        if c > 0 and coefs[n] > 0.0:
            degrees.append(n)
            counts.append(c)
            scales.append(float(scales_all[n]))

    return CtrPlan(
        degrees=tuple(degrees),
        counts=tuple(counts),
        scales=tuple(scales),
        const=float(np.sqrt(a0)) if (a0 > 0.0 and not h01) else 0.0,
        h01=h01,
        h01_a0=a0 if h01 else 0.0,
        h01_a1=a1 if h01 else 0.0,
        input_dim=input_dim,
        num_random=num_features,
        coefs_host=tuple(float(c) for c in coefs_diag),
        seed=seed,
    )


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def init_ctr_params(
    plan: CtrPlan, key: jax.Array, dtype=jnp.float32
) -> Dict[str, jax.Array]:
    """Complex Rademacher rows for one plan instance, as two REAL tensors.

    Returns ``{"wr": dtype [total_rows, d], "wi": dtype [total_rows, d]}``
    with ``wr + i*wi`` uniform over the fourth roots of unity
    ``{1, i, -1, -i}`` — entries are EXACT 0.0 / +-1.0 floats (drawn as an
    int in {0..3}, not via cos/sin, so no float rounding enters the draws).
    Row layout is bucket-major then feature-major, exactly like RM omegas:
    rows ``[off_n + i*n, off_n + (i+1)*n)`` belong to complex feature i of
    degree bucket n. Like RM omegas these are frozen model constants.
    """
    t = jax.random.randint(key, (plan.total_rows, plan.input_dim), 0, 4)
    wr = jnp.where(t == 0, 1.0, jnp.where(t == 2, -1.0, 0.0)).astype(dtype)
    wi = jnp.where(t == 1, 1.0, jnp.where(t == 3, -1.0, 0.0)).astype(dtype)
    return {"wr": wr, "wi": wi}


# ---------------------------------------------------------------------------
# packing for the fused kernel
# ---------------------------------------------------------------------------
def pack_ctr(
    plan: CtrPlan, params: Dict[str, jax.Array]
) -> Tuple[jax.Array, jax.Array]:
    """Flat rows ``[total_rows, d]`` x2 -> fused ``(wr, wi)`` tensors.

    Each output is ``[max_degree, num_complex, d]``: complex column f's
    product slots are ``wr/wi[0:col_degree[f], f, :]``; unused slots are
    zero (masked inside the kernel, never multiplied). Pure
    reshape/pad/concat — same traffic note as ``core.plan.pack_omegas``:
    callers applying one plan repeatedly should pack once and pass
    ``packed=`` to ``apply_ctr_plan``.
    """
    d = plan.input_dim
    k = plan.max_degree

    def _pack(flat):
        parts = []
        off = 0
        for n, c in zip(plan.degrees, plan.counts):
            rows = flat[off : off + c * n].reshape(c, n, d)
            off += c * n
            parts.append(jnp.pad(rows, ((0, 0), (0, k - n), (0, 0))))
        if not parts:
            return jnp.zeros((k, 0, d), flat.dtype)
        packed = jnp.concatenate(parts, axis=0)                 # [Fc, k, d]
        return jnp.transpose(packed, (1, 0, 2))                 # [k, Fc, d]

    return _pack(params["wr"]), _pack(params["wi"])


# ---------------------------------------------------------------------------
# application — ONE fused launch (or the jnp complex oracle)
# ---------------------------------------------------------------------------
def apply_ctr_plan(
    plan: CtrPlan,
    params: Dict[str, jax.Array],
    x: jax.Array,
    accum_dtype=jnp.float32,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
    packed: Optional[Tuple[jax.Array, jax.Array]] = None,
    precision=None,
) -> jax.Array:
    """Featurize ``x [..., d] -> [..., plan.output_dim]``.

    The deterministic prefix columns (h01 block / degree-0 const) are exact
    jnp fills; the complex buckets run as ONE fused Pallas launch
    (``repro.kernels.ctr_feature``) on TPU, or the ``complex64`` oracle
    (``repro.ctr.ref.ctr_blocks_ref``) elsewhere. Mirrors
    ``core.plan.apply_plan``'s contract so the estimator registry exposes
    all families behind one ``apply``; ``packed`` short-circuits
    ``pack_ctr`` for callers that cache the packed tensors.

    ``precision`` selects the input dtype policy: under ``"bf16"`` x and the
    packed ``wr``/``wi`` tensors enter the kernel in bf16 — the fourth-root
    values {0, +-1} are exact in bf16, so only x is rounded — while both
    accumulators stay fp32. The complex64 oracle has no bf16 path, so
    off-Pallas the policy only rounds x.
    """
    from repro.common.dtypes import resolve_precision
    from repro.ctr.ref import ctr_blocks_ref
    from repro.kernels.ctr_feature.ops import ctr_feature_fused

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
    xf = x.reshape(-1, plan.input_dim).astype(accum_dtype)
    feats = []
    if plan.h01:
        feats.append(jnp.full((xf.shape[0], 1), np.sqrt(plan.h01_a0),
                              dtype=accum_dtype))
        feats.append(jnp.asarray(np.sqrt(plan.h01_a1), accum_dtype)
                     * xf.astype(compute_dtype).astype(accum_dtype))
    if plan.const != 0.0:
        feats.append(jnp.full((xf.shape[0], 1), plan.const,
                              dtype=accum_dtype))
    if plan.num_complex:
        if use_pallas:
            wr, wi = (packed if packed is not None
                      else pack_ctr(plan, params))
            z = ctr_feature_fused(
                xf.astype(compute_dtype),
                wr.astype(compute_dtype), wi.astype(compute_dtype),
                jnp.asarray(plan.column_degrees()),
                jnp.asarray(plan.column_scales()),
                use_pallas=True, interpret=interpret,
            ).astype(accum_dtype)
        else:
            z = ctr_blocks_ref(
                plan, params, xf.astype(compute_dtype)
            ).astype(accum_dtype)
        feats.append(z)
    if not feats:
        # fully degenerate plan (a_0 = 0 and the halved budget funded no
        # complex features): a valid 0-column map, not a concat error —
        # its Gram estimate is identically 0, matching output_dim == 0.
        return jnp.zeros((*batch_shape, 0), accum_dtype)
    out = jnp.concatenate(feats, axis=-1)
    return out.reshape(*batch_shape, out.shape[-1])
