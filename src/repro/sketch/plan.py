"""SketchPlan — TensorSketch plans for dot-product kernels.

TensorSketch (Pham & Pagh, KDD 2013) approximates the degree-n component of a
dot product kernel ``f(<x,y>) = sum_n a_n <x,y>^n`` with the circular
convolution of ``n`` independent CountSketches:

    S_n(x) = IFFT( prod_{j<n} FFT( C_j x ) ),   E[<S_n(x), S_n(y)>] = <x,y>^n.

Where Random Maclaurin (repro.core.plan) pays ``O(d)`` Rademacher projections
per *column*, TensorSketch pays ``O(d + F_n log F_n)`` per degree *block* —
the whole block jointly estimates one monomial, so its width ``F_n`` is a
variance knob, not a sum of independent estimators.

This module mirrors ``repro.core.plan`` deliberately:

    degree measure  ->  width allocation (largest remainder)  ->  sqrt(a_n)
                    ->  packed frequency-domain layout (DESIGN.md §9)

A ``SketchPlan`` is a hashable NamedTuple (jit-static). Column layout:

    [ h01 const | h01 identity block | degree-0 const | degree blocks asc ]

The deterministic prefix columns are exact (zero variance) and computed
outside the kernels; the random section is the concatenation of the degree
blocks in ascending degree order.

Frequency-domain packing (``pack_sketch``): because the FFT is linear, the
per-slot transform ``FFT(C_j x)`` is a dense complex projection

    FFT(C_j x)[f] = sum_i s_j(i) exp(-2 pi i f h_j(i) / F_n) x_i = <G_j[f], x>

so the WHOLE map becomes (i) a masked complex running product over degree
slots — exactly the ``rm_feature_fused`` structure with two (real, imag)
accumulators — followed by (ii) one block-diagonal inverse-DFT matmul. Both
stages are MXU matmuls, which is what ``tensor_sketch_fused`` fuses into one
Pallas launch; the ``jnp.fft`` path in ``repro.sketch.ref`` is the
O(F log F) oracle it is checked against.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.maclaurin import DotProductKernel
from repro.core.plan import BIAS_TAIL_DEGREES, allocate_features

__all__ = [
    "SketchPlan",
    "make_sketch_plan",
    "init_sketch_params",
    "pack_sketch",
    "apply_sketch_plan",
]


class SketchPlan(NamedTuple):
    """Hashable TensorSketch plan: static through jit/scan.

    ``degrees``/``counts``/``scales`` describe the degree >= 1 sketch blocks
    (ascending): block n has sketch width ``counts[i]`` and block scale
    ``scales[i] = sqrt(a_n)`` (the whole block estimates ``a_n <x,y>^n``).
    ``seed`` records the width-allocation seed so plans reproduce across
    hosts (see ``to_json``).
    """

    degrees: Tuple[int, ...]
    counts: Tuple[int, ...]           # sketch width F_n per degree block
    scales: Tuple[float, ...]         # sqrt(a_n) per block
    const: float                      # exact degree-0 column (0.0 when absent)
    h01: bool
    h01_a0: float
    h01_a1: float
    input_dim: int
    num_random: int                   # D, the total feature budget
    # a_0..a_{n_max + BIAS_TAIL_DEGREES} (tail window: bias diagnostics only)
    coefs_host: Tuple[float, ...]
    seed: int                         # allocation seed (reproducibility)

    # -- sizes ---------------------------------------------------------------
    @property
    def num_funcs(self) -> int:
        """CountSketch hash functions backing the blocks: sum_n n."""
        return int(sum(self.degrees))

    @property
    def max_degree(self) -> int:
        return max(self.degrees) if self.degrees else 0

    @property
    def num_sketch_cols(self) -> int:
        return int(sum(self.counts))

    @property
    def num_prefix_columns(self) -> int:
        pre = 0
        if self.h01:
            pre += 1 + self.input_dim
        if self.const != 0.0:
            pre += 1
        return pre

    @property
    def output_dim(self) -> int:
        return self.num_prefix_columns + self.num_sketch_cols

    # -- fused column layout (host-side, static; random section only) --------
    def column_degrees(self) -> np.ndarray:
        """Per sketch column product depth, int32 ``[num_sketch_cols]``."""
        deg = []
        for n, c in zip(self.degrees, self.counts):
            deg.extend([n] * c)
        return np.asarray(deg, dtype=np.int32)

    def column_scales(self) -> np.ndarray:
        """Per sketch column scale sqrt(a_n), float32 ``[num_sketch_cols]``."""
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

    # -- serialization (shared body with FeaturePlan) ------------------------
    def to_json(self) -> str:
        """Full plan state (seed + realized allocation included) as JSON."""
        from repro.core.plan import plan_to_json

        return plan_to_json(self)

    @classmethod
    def from_json(cls, s: str) -> "SketchPlan":
        """Inverse of ``to_json`` (lossless: conformance-tested)."""
        from repro.core.plan import plan_from_json

        return plan_from_json(cls, s)


def make_sketch_plan(
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
) -> SketchPlan:
    """Allocate sketch widths across degrees of the Maclaurin measure.

    The SAME Taylor-coefficient measure machinery as the RM estimator
    (``core.feature_map.degree_measure``) splits the feature budget; here the
    per-degree count is a sketch WIDTH (variance knob), not a number of
    independent columns, so widths are always deterministic largest-remainder
    rounding — ``stratified`` is accepted for estimator-protocol uniformity
    and ignored. ``seed`` is recorded on the plan.
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
    counts_all, _ = allocate_features(coefs, q, budget, stratified=True,
                                      seed=seed)

    degrees, counts, scales = [], [], []
    for n in range(min_degree, n_max + 1):
        c = int(counts_all[n])
        if c > 0 and coefs[n] > 0.0:
            degrees.append(n)
            counts.append(c)
            scales.append(float(np.sqrt(coefs[n])))

    return SketchPlan(
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
def init_sketch_params(
    plan: SketchPlan, key: jax.Array, dtype=jnp.float32
) -> Dict[str, jax.Array]:
    """CountSketch hash tensors for one plan instance.

    Returns ``{"h": int32 [num_funcs, d], "s": dtype [num_funcs, d]}``. Rows
    are block-major then slot-major: rows ``[off_i, off_i + n)`` are the n
    independent CountSketches of degree block n (``off_i = sum of earlier
    degrees``); row values of block i live in ``[0, counts[i])``. Fully random
    hash tables (stronger than the 2-/3-wise independence TensorSketch
    requires) — like RM omegas, these are model constants, never trained.
    """
    d = plan.input_dim
    hs, ss = [], []
    for n, c in zip(plan.degrees, plan.counts):
        for _ in range(n):
            key, kh, ks = jax.random.split(key, 3)
            hs.append(jax.random.randint(kh, (d,), 0, c, dtype=jnp.int32))
            ss.append(2.0 * jax.random.bernoulli(ks, 0.5, (d,)).astype(dtype)
                      - 1.0)
    if not hs:
        return {
            "h": jnp.zeros((0, d), jnp.int32),
            "s": jnp.zeros((0, d), dtype),
        }
    return {"h": jnp.stack(hs), "s": jnp.stack(ss)}


# ---------------------------------------------------------------------------
# frequency-domain packing for the fused kernel
# ---------------------------------------------------------------------------
def pack_sketch(
    plan: SketchPlan, params: Dict[str, jax.Array], dtype=jnp.float32
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Hash tensors -> fused tensors ``(wr, wi, mr, mi)``.

    * ``wr/wi [max_degree, Fs, d]`` — real/imag of the per-slot DFT'd
      CountSketch projections: column f of block (n, c) with local frequency
      ``fl`` and slot j holds ``s_j(i) * exp(-2 pi i fl h_j(i) / c)``.
      Slots ``j >= n`` are zero (masked by ``column_degrees`` in the kernel).
    * ``mr/mi [Fs, Fs]`` — the block-diagonal inverse-DFT:
      ``M[g, f] = exp(+2 pi i g f / c) / c`` within a block, 0 across blocks.
      ``real(M P) = mr @ Pr - mi @ Pi`` recovers the circular convolution.

    Phase indices are reduced mod c in int32 BEFORE the float angle (exact:
    ``f * h < c^2 < 2^31`` for any practical width), so large frequencies
    don't lose precision in float32.
    """
    d = plan.input_dim
    k = plan.max_degree
    fs = plan.num_sketch_cols
    wr = jnp.zeros((k, fs, d), dtype)
    wi = jnp.zeros((k, fs, d), dtype)
    mr = jnp.zeros((fs, fs), dtype)
    mi = jnp.zeros((fs, fs), dtype)
    col = 0
    row = 0
    for n, c in zip(plan.degrees, plan.counts):
        freqs = jnp.arange(c, dtype=jnp.int32)
        for j in range(n):
            h = params["h"][row + j]                       # [d] int32
            s = params["s"][row + j].astype(dtype)         # [d]
            ph = (freqs[:, None] * h[None, :]) % c         # [c, d] exact
            ang = (2.0 * np.pi / c) * ph.astype(dtype)
            wr = wr.at[j, col : col + c, :].set(s[None, :] * jnp.cos(ang))
            wi = wi.at[j, col : col + c, :].set(-s[None, :] * jnp.sin(ang))
        gf = (freqs[:, None] * freqs[None, :]) % c         # [c, c] exact
        ang = (2.0 * np.pi / c) * gf.astype(dtype)
        mr = mr.at[col : col + c, col : col + c].set(jnp.cos(ang) / c)
        mi = mi.at[col : col + c, col : col + c].set(jnp.sin(ang) / c)
        col += c
        row += n
    return wr, wi, mr, mi


# ---------------------------------------------------------------------------
# application — ONE fused launch (or the jnp.fft oracle)
# ---------------------------------------------------------------------------
def apply_sketch_plan(
    plan: SketchPlan,
    params: Dict[str, jax.Array],
    x: jax.Array,
    accum_dtype=jnp.float32,
    use_pallas=None,
    interpret=None,
    packed=None,
    precision=None,
) -> jax.Array:
    """Featurize ``x [..., d] -> [..., plan.output_dim]``.

    The deterministic prefix columns (h01 block / degree-0 const) are exact
    jnp fills; the sketch blocks run as ONE fused Pallas launch
    (``repro.kernels.tensor_sketch``) on TPU, or the ``jnp.fft`` oracle
    elsewhere. Mirrors ``core.plan.apply_plan``'s contract so the estimator
    registry can expose both behind one ``apply``: ``packed`` short-circuits
    ``pack_sketch`` — the frequency-domain tensors depend only on the frozen
    hash tables, so callers applying one plan repeatedly (per-layer featurize,
    decode steps) should pack once and pass ``packed=(wr, wi, mr, mi)``.

    ``precision`` selects the input dtype policy: under ``"bf16"`` x and the
    four packed frequency-domain tensors enter the fused launch in bf16
    (accumulation stays fp32 inside the kernel). The packing itself always
    runs in fp32 — the cos/sin phases are computed at full precision, then
    rounded ONCE to the storage dtype. The ``jnp.fft`` oracle has no bf16
    path (complex bf16 doesn't exist), so off-Pallas the policy only rounds
    x; fp32/complex64 carries the rest.
    """
    from repro.common.dtypes import resolve_precision
    from repro.kernels.tensor_sketch.ops import tensor_sketch_fused
    from repro.sketch.ref import tensor_sketch_blocks_ref

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
    if plan.num_sketch_cols:
        if use_pallas:
            wr, wi, mr, mi = (packed if packed is not None
                              else pack_sketch(plan, params,
                                               dtype=jnp.float32))
            z = tensor_sketch_fused(
                xf.astype(compute_dtype),
                wr.astype(compute_dtype), wi.astype(compute_dtype),
                jnp.asarray(plan.column_degrees()),
                mr.astype(compute_dtype), mi.astype(compute_dtype),
                jnp.asarray(plan.column_scales()),
                use_pallas=True, interpret=interpret,
            ).astype(accum_dtype)
        else:
            z = tensor_sketch_blocks_ref(
                plan, params, xf.astype(compute_dtype)
            ).astype(accum_dtype)
        feats.append(z)
    out = jnp.concatenate(feats, axis=-1)
    return out.reshape(*batch_shape, out.shape[-1])
