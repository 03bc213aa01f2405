"""StructuredPlan — Hadamard-structured (HD) sublinear feature maps.

Choromanski & Sindhwani, *Recycling Randomness with Structure for Sublinear
time Kernel Expansions* (2016), replace the paper's i.i.d. Rademacher rows
with STRUCTURED projection stacks: each degree-n product slot applies

    P_j x = D2_j H D1_j x,

where ``D1_j, D2_j`` are independent diagonal Rademacher matrices and ``H``
is the (unnormalized, +-1) Sylvester Walsh-Hadamard matrix of size
``d_pad = 2^ceil(log2 d)``. One stack produces ``d_pad`` output columns per
slot from only ``2 d_pad`` random signs, and applies in ``O(d_pad log
d_pad)`` via the butterfly WHT instead of the ``O(d_pad^2)`` of a dense
draw — across F features the apply cost drops from O(dF) to O(F log d).

Unbiasedness is column-exact: output column f of one slot is
``<h_f ∘ d1, x>`` with ``h_f`` the (+-1) f-th Hadamard row, and
``E[(h_f ∘ d1)_a (h_f ∘ d1)_b] = h_fa h_fb E[d1_a d1_b] = delta_ab``
— every single column is distributed EXACTLY like one RM Rademacher
projection (the outer ``D2`` sign is a per-column Rademacher that cancels
in products of independent slots). Degree-n features multiply n
independent stacks, so ``E[z_f(x) z_f(y)] = <x,y>^n`` with zero-padded
inputs and the SAME ``sqrt(a_n / c_n)`` scales as RM. What changes is only
the joint law of the d_pad columns WITHIN one stack (they share d1/d2) —
the cross-column covariance argument lives in DESIGN.md §15.

This module mirrors ``repro.ctr.plan`` exactly:

    degree measure  ->  per-degree feature allocation  ->  sqrt(a_n / c_n)
                    ->  packed fused layout (two sign tensors, DESIGN.md §15)

A ``StructuredPlan`` is a hashable NamedTuple (jit-static). Column layout:

    [ h01 const | h01 identity block | degree-0 const
      | random columns, buckets ascending ]

Bucket n funds ``ceil(c_n / d_pad)`` independent stacks of ``d_pad``
columns each; the trailing ``S_n d_pad - c_n`` surplus columns of the last
stack are computed but carry scale 0 and are sliced off by ``apply`` —
allocation counts stay exactly the degree-measure counts, so plans,
output_dim and truncation diagnostics are budget-identical to RM.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.maclaurin import DotProductKernel
from repro.core.plan import BIAS_TAIL_DEGREES, allocate_features

__all__ = [
    "StructuredPlan",
    "make_structured_plan",
    "init_structured_params",
    "pack_structured",
    "apply_structured_plan",
]


def _next_pow2(n: int) -> int:
    m = 1
    while m < n:
        m *= 2
    return m


class StructuredPlan(NamedTuple):
    """Hashable Hadamard-structured feature-map plan: static through jit.

    ``degrees``/``counts``/``scales`` describe the degree >= 1 REAL feature
    buckets (ascending): bucket n holds ``counts[i]`` features of
    per-feature scale ``scales[i]``, backed by ``ceil(counts[i] / d_pad)``
    independent D2·H·D1 stacks per degree slot. ``seed`` records the
    ``allocate_features`` seed so plans reproduce across hosts (``to_json``
    carries every field).
    """

    degrees: Tuple[int, ...]
    counts: Tuple[int, ...]           # real features per degree bucket
    scales: Tuple[float, ...]         # per-feature scale sqrt(a_n / c_n)
    const: float                      # exact degree-0 column (0.0 when absent)
    h01: bool
    h01_a0: float
    h01_a1: float
    input_dim: int
    num_random: int                   # F, the real feature budget
    # a_0..a_{n_max + BIAS_TAIL_DEGREES} (tail window: bias diagnostics only)
    coefs_host: Tuple[float, ...]
    seed: int                         # allocation seed (reproducibility)

    # -- sizes ---------------------------------------------------------------
    @property
    def d_pad(self) -> int:
        """Hadamard size: next power of two >= input_dim (x is zero-padded;
        zero padding is exact — padded coordinates never contribute)."""
        return _next_pow2(max(self.input_dim, 1))

    @property
    def stacks_per_bucket(self) -> Tuple[int, ...]:
        """Independent D2·H·D1 stacks funding each bucket:
        ``ceil(c_n / d_pad)``."""
        m = self.d_pad
        return tuple((c + m - 1) // m for c in self.counts)

    @property
    def total_stacks(self) -> int:
        return int(sum(self.stacks_per_bucket))

    @property
    def total_slots(self) -> int:
        """Diagonal-sign rows backing the buckets: ``sum_n S_n * n`` (each
        stack draws one (d1, d2) pair per degree slot)."""
        return int(sum(s * n
                       for s, n in zip(self.stacks_per_bucket, self.degrees)))

    @property
    def max_degree(self) -> int:
        """Product depth of the packed layout (0 for a const-only plan)."""
        return max(self.degrees) if self.degrees else 0

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
    def num_random_cols(self) -> int:
        """Real random columns surviving the surplus slice: sum of counts."""
        return int(sum(self.counts))

    @property
    def padded_num_cols(self) -> int:
        """Columns the fused launch actually computes:
        ``total_stacks * d_pad`` (surplus columns included)."""
        return self.total_stacks * self.d_pad

    @property
    def output_dim(self) -> int:
        """Real output columns: prefix + allocated features."""
        return self.num_prefix_columns + self.num_random_cols

    # -- fused column layout (host-side, static; padded section) -------------
    def padded_column_degrees(self) -> np.ndarray:
        """Per PADDED column product depth, int32 ``[padded_num_cols]``
        (surplus columns keep their bucket's degree; their zero scale
        removes them before the slice)."""
        m = self.d_pad
        deg = []
        for n, s in zip(self.degrees, self.stacks_per_bucket):
            deg.extend([n] * (s * m))
        return np.asarray(deg, dtype=np.int32)

    def padded_column_scales(self) -> np.ndarray:
        """Per PADDED column scale, float32 ``[padded_num_cols]``: the
        bucket scale on its first ``c_n`` columns (stack-major layout keeps
        them contiguous), 0.0 on the surplus tail."""
        m = self.d_pad
        sc = []
        for scale, c, s in zip(self.scales, self.counts,
                               self.stacks_per_bucket):
            sc.extend([float(scale)] * c)
            sc.extend([0.0] * (s * m - c))
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

    # -- serialization (shared body with FeaturePlan/CtrPlan) ----------------
    def to_json(self) -> str:
        """Full plan state (seed + realized allocation included) as JSON."""
        from repro.core.plan import plan_to_json

        return plan_to_json(self)

    @classmethod
    def from_json(cls, s: str) -> "StructuredPlan":
        """Inverse of ``to_json`` (lossless: conformance-tested)."""
        from repro.core.plan import plan_from_json

        return plan_from_json(cls, s)


def make_structured_plan(
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
) -> StructuredPlan:
    """Allocate structured features across degrees of the Maclaurin measure.

    Args mirror ``core.plan.make_feature_plan`` (the estimator-registry
    ``make_plan`` signature). The budget split is IDENTICAL to RM — the
    same degree measure, the same ``allocate_features`` counts, the same
    ``sqrt(a_n / c_n)`` scales (each structured column is distributed like
    one RM column, see the module docstring) — only the backing randomness
    changes: ``ceil(c_n / d_pad)`` (d1, d2) sign-pair stacks per degree
    slot instead of ``c_n * n`` dense Rademacher rows.

    Returns the hashable ``StructuredPlan``.
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
    budget = max(num_features - prefix, 0)
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

    return StructuredPlan(
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
def init_structured_params(
    plan: StructuredPlan, key: jax.Array, dtype=jnp.float32
) -> Dict[str, jax.Array]:
    """Diagonal Rademacher signs for one plan instance.

    Returns ``{"d1": dtype [total_slots, d_pad], "d2": [total_slots,
    d_pad]}`` — slot s of a stack applies ``diag(d2[s]) H diag(d1[s])``.
    Entries are EXACT +-1.0 floats in any dtype. Slot layout is
    bucket-major, then stack-major, then degree-slot: rows ``[off_n + i*n,
    off_n + (i+1)*n)`` belong to stack i of degree bucket n. Pure traceable
    jax (one ``bernoulli`` draw), so the sharded path can fold keys and
    draw INSIDE ``shard_map`` (repro.distributed.estimator). Like RM omegas
    these are frozen model constants.
    """
    t = jax.random.bernoulli(key, 0.5, (2, plan.total_slots, plan.d_pad))
    signs = jnp.where(t, 1.0, -1.0).astype(dtype)
    return {"d1": signs[0], "d2": signs[1]}


# ---------------------------------------------------------------------------
# packing for the fused kernel
# ---------------------------------------------------------------------------
def pack_structured(
    plan: StructuredPlan, params: Dict[str, jax.Array]
) -> Tuple[jax.Array, jax.Array]:
    """Flat slots ``[total_slots, d_pad]`` x2 -> fused ``(d1, d2)`` tensors.

    Each output is ``[max_degree, total_stacks, d_pad]``: stack i's product
    slots are ``d1/d2[0:stack_degree[i], i, :]``; unused slots are zero
    (masked inside the kernel, never multiplied). Pure reshape/pad/concat —
    same traffic note as ``core.plan.pack_omegas``: callers applying one
    plan repeatedly should pack once and pass ``packed=`` to
    ``apply_structured_plan``.
    """
    m = plan.d_pad
    k = plan.max_degree

    def _pack(flat):
        parts = []
        off = 0
        for n, s in zip(plan.degrees, plan.stacks_per_bucket):
            rows = flat[off : off + s * n].reshape(s, n, m)
            off += s * n
            parts.append(jnp.pad(rows, ((0, 0), (0, k - n), (0, 0))))
        if not parts:
            return jnp.zeros((k, 0, m), flat.dtype)
        packed = jnp.concatenate(parts, axis=0)            # [stacks, k, m]
        return jnp.transpose(packed, (1, 0, 2))            # [k, stacks, m]

    return _pack(params["d1"]), _pack(params["d2"])


# ---------------------------------------------------------------------------
# application — ONE fused launch (or the jnp dense-WHT oracle)
# ---------------------------------------------------------------------------
def apply_structured_plan(
    plan: StructuredPlan,
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
    jnp fills; the structured buckets run as ONE fused Pallas launch
    (``repro.kernels.structured_feature``) on TPU, or the dense-WHT matmul
    oracle (``repro.structured.ref.structured_blocks_ref``) elsewhere.
    Either path computes the padded ``total_stacks * d_pad`` columns; the
    surplus tail of each bucket (zero scale by construction) is dropped
    here with one contiguous slice per bucket. Mirrors
    ``core.plan.apply_plan``'s contract so the estimator registry exposes
    all families behind one ``apply``; ``packed`` short-circuits
    ``pack_structured`` for callers that cache the packed tensors.

    ``precision`` selects the input dtype policy: under ``"bf16"`` x and
    the sign tensors enter the kernel in bf16 — the +-1 signs are exact in
    bf16, so only x is rounded — while the running-product accumulator
    stays fp32 (the kernel widens on load).
    """
    from repro.common.dtypes import resolve_precision
    from repro.kernels.structured_feature.ops import structured_feature_fused
    from repro.structured.ref import structured_blocks_ref

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
    m = plan.d_pad
    feats = []
    if plan.h01:
        feats.append(jnp.full((xf.shape[0], 1), np.sqrt(plan.h01_a0),
                              dtype=accum_dtype))
        feats.append(jnp.asarray(np.sqrt(plan.h01_a1), accum_dtype)
                     * xf.astype(compute_dtype).astype(accum_dtype))
    if plan.const != 0.0:
        feats.append(jnp.full((xf.shape[0], 1), plan.const,
                              dtype=accum_dtype))
    if plan.num_random_cols:
        if use_pallas:
            d1, d2 = (packed if packed is not None
                      else pack_structured(plan, params))
            # zero-pad x to the Hadamard size (exact in any dtype)
            xp = jnp.pad(xf, ((0, 0), (0, m - plan.input_dim)))
            z = structured_feature_fused(
                xp.astype(compute_dtype),
                d1.astype(compute_dtype), d2.astype(compute_dtype),
                jnp.asarray(plan.padded_column_degrees()),
                jnp.asarray(plan.padded_column_scales()),
                use_pallas=True, interpret=interpret,
            ).astype(accum_dtype)
        else:
            z = structured_blocks_ref(
                plan, params, xf.astype(compute_dtype)
            ).astype(accum_dtype)
        # drop each bucket's surplus tail: the real columns are the FIRST
        # c_n of its stack-major padded run, so one slice per bucket
        parts, off = [], 0
        for c, s in zip(plan.counts, plan.stacks_per_bucket):
            parts.append(z[:, off : off + c])
            off += s * m
        feats.append(parts[0] if len(parts) == 1
                     else jnp.concatenate(parts, axis=-1))
    if not feats:
        # fully degenerate plan (a_0 = 0 and no bucket funded): a valid
        # 0-column map, not a concat error — its Gram estimate is
        # identically 0, matching output_dim == 0.
        return jnp.zeros((*batch_shape, 0), accum_dtype)
    out = jnp.concatenate(feats, axis=-1)
    return out.reshape(*batch_shape, out.shape[-1])
