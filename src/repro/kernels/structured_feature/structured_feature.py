"""Pallas TPU kernel for fused structured (Hadamard) feature application.

``structured_feature_fused_pallas`` applies every degree bucket of a
``StructuredPlan`` in ONE launch (DESIGN.md §15): a masked running product
over degree slots — the ``rm_feature_fused`` loop — where slot j's
projection is not an MXU matmul against drawn rows but the in-VMEM
butterfly Walsh-Hadamard transform of the diagonally-signed input,

    P_j = d2_j ∘ WHT( d1_j ∘ x ),

computed per (batch, stack) tile in O(d_pad log d_pad) adds on the VPU —
the sublinear-time structure of Choromanski & Sindhwani (2016). The
butterfly matches the SYLVESTER Hadamard order exactly (the dense-matmul
oracle in ``repro.structured.ref`` is the ground truth), unrolling
log2(d_pad) lane-roll stages at trace time.

The grid tiles (batch, stack): each feature tile covers ``block_s`` whole
stacks of ``d_pad`` columns laid side by side on the lane axis — the sign
tensors enter as ``[max_degree, S * d_pad]`` rows, like the per-column
degree/scale metadata — so every operand stays 2-D. Columns are laid out
in ascending degree order, so each tile's loop exits at the TILE's max
depth, not the global one. The accumulator is an
fp32 VMEM buffer; bf16 inputs are widened once on load (bf16-in /
fp32-accum, same policy as the other feature kernels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wht(v: jax.Array, m: int) -> jax.Array:
    """Butterfly Walsh-Hadamard transform of every length-``m`` lane segment
    of ``v [rows, n]`` (``m`` a static power of two dividing ``n``):
    Sylvester order, unnormalized (+-1 entries).

    Stage ``h`` pairs lane ``i`` with ``i ^ h`` — always inside ``i``'s own
    segment — and maps ``(a, b) -> (a + b, a - b)``. The partner comes from
    lane rolls, so the transform stays in the native (sublane, lane) layout
    (no reshape of the lane dim, which the TPU lowering refuses). Which of
    the two rolls holds lane ``i ^ h`` is read off a rolled iota, so the
    result does not depend on the roll direction convention.
    """
    n = v.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    h = 1
    while h < m:
        fwd = pltpu.roll(v, h, 1)
        bwd = pltpu.roll(v, n - h, 1)
        from_fwd = pltpu.roll(lane, h, 1) == (lane ^ h)
        partner = jnp.where(from_fwd, fwd, bwd)
        v = jnp.where((lane & h) == 0, v + partner, partner - v)
        h *= 2
    return v


def _structured_fused_kernel(x_ref, d1_ref, d2_ref, deg_ref, scale_ref,
                             o_ref, *, m: int):
    # Widen once on load: the WHT is pure adds/subs, so fp32 intermediates
    # keep the running product exactly fp32-accumulated under bf16 inputs.
    x = x_ref[...].astype(jnp.float32)            # [bm, m]
    deg = deg_ref[...]                            # [1, bs * m] int32
    bs = deg.shape[-1] // m
    # every stack of the tile transforms the same input rows
    xs = x if bs == 1 else jnp.concatenate([x] * bs, axis=-1)

    def step(j, acc):
        d1 = d1_ref[pl.ds(j, 1), :].astype(jnp.float32)   # [1, bs * m]
        d2 = d2_ref[pl.ds(j, 1), :].astype(jnp.float32)
        p = _wht(xs * d1, m) * d2
        return jnp.where(j < deg, acc * p, acc)

    depth = jnp.max(deg)                          # tile-local product depth
    acc = jax.lax.fori_loop(0, depth, step, jnp.ones(xs.shape, jnp.float32))
    scale = scale_ref[...].astype(jnp.float32)
    o_ref[...] = (acc * scale).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_s", "interpret")
)
def structured_feature_fused_pallas(
    x: jax.Array,          # [B, d_pad]          (B pre-padded to block_b)
    d1: jax.Array,         # [max_degree, S, d_pad]  (S pre-padded to block_s)
    d2: jax.Array,         # [max_degree, S, d_pad]
    col_deg: jax.Array,    # [S * d_pad] int32   (padding stacks: 0)
    col_scale: jax.Array,  # [S * d_pad] float32 (padding stacks: 0)
    *,
    block_b: int = 256,
    block_s: int = 8,
    interpret: bool = False,
) -> jax.Array:            # [B, S * d_pad] float32
    """One launch over (batch, stack) tiles; feature tiles are whole stacks.

    ``col_deg``/``col_scale`` are per PADDED column (``S * d_pad`` entries,
    stack-major) — the ops-layer wrapper builds them from the plan and
    slices off both the pad stacks and each bucket's surplus columns after
    the launch, keeping the kernel free of bucket bookkeeping.
    """
    b, m = x.shape
    k, s, _ = d1.shape
    assert b % block_b == 0 and s % block_s == 0, (b, s, block_b, block_s)
    grid = (b // block_b, s // block_s)
    bf = block_s * m
    # stacks laid out along the lane axis: [max_degree, S * d_pad]
    return pl.pallas_call(
        functools.partial(_structured_fused_kernel, m=m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, m), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bf), lambda i, j: (0, j)),
            pl.BlockSpec((k, bf), lambda i, j: (0, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, bf), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, s * m), jnp.float32),
        interpret=interpret,
    )(x, d1.reshape(k, s * m), d2.reshape(k, s * m),
      col_deg.reshape(1, s * m), col_scale.reshape(1, s * m))
