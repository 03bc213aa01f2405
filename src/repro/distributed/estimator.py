"""Sharded estimator execution over a device mesh.

The paper's embedding makes dot-product kernels *linear*: after featurizing,
``K(x, y) ~= <Z(x), Z(y)>``, and an inner product is embarrassingly shardable.
This module partitions the random-feature budget over the ``"rm_features"``
mesh axis, uniformly for EVERY entry of the estimator registry:

    * a global budget of D features over S shards becomes S independent
      sub-maps of D/S features each, built from ONE per-shard plan (the same
      hashable plan on every shard, so shard_map traces once) and per-shard
      params drawn with ``jax.random.fold_in(key, shard)`` — shard s's draws
      depend only on (key, s), never on which device holds them;
    * ``Z(x) = concat_s Z_s(x) / sqrt(S)`` — each sub-map is an unbiased
      estimator of the kernel, so their concatenation at 1/sqrt(S) scale is
      the unbiased S-fold average (deterministic prefix columns are exact
      under the same scaling: S copies of ``sqrt(a_0)/sqrt(S)`` contribute
      exactly a_0 to the Gram);
    * ``estimate_gram`` never materializes the concatenation: each shard
      computes its partial Gram ``Z_s(X) Z_s(Y)^T / S`` and ONE ``psum``
      over the feature axis reduces them.

Bit-identity contract: the mesh path and the single-device reference run the
SAME per-shard computation from the SAME folded keys in the SAME concat
order, so ``sharded=True`` vs ``sharded=False`` apply is bit-identical;
only the Gram psum may reassociate the cross-shard sum (parity to ~1e-5 in
float32 — tests/test_distributed_estimators.py locks both down).

The registry is the only coupling point: any estimator satisfying the
five-function protocol (``make_plan``/``init_params``/``apply``/
``output_dim``/``truncation_bias``) shards with no family-specific code.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import registry
from repro.distributed.sharding import (
    FEATURE_AXIS,
    estimator_param_specs,
    shard_map,
)
from repro.kernels.common import default_interpret

__all__ = [
    "FEATURE_AXIS",
    "shard_init_params",
    "sharded_apply",
    "sharded_estimate_gram",
    "ShardedFeatureMap",
    "make_sharded_feature_map",
]


def _unstack(params: Any) -> Any:
    """Strip the leading size-1 shard dim of a shard-local param tree."""
    return jax.tree_util.tree_map(lambda a: a[0], params)


def _take(params: Any, s: int) -> Any:
    return jax.tree_util.tree_map(lambda a: a[s], params)


def _num_shards(params: Any) -> int:
    return int(jax.tree_util.tree_leaves(params)[0].shape[0])


# ---------------------------------------------------------------------------
# init — per-shard RNG via fold_in on the mesh coordinate
# ---------------------------------------------------------------------------
def shard_init_params(
    name: str,
    plan: Any,
    key: jax.Array,
    num_shards: int,
    *,
    dtype=jnp.float32,
    mesh: Optional[Mesh] = None,
    axis: str = FEATURE_AXIS,
) -> Any:
    """Stacked per-shard estimator params: leaves are ``[num_shards, ...]``.

    Shard s's params are ``init_params(plan, fold_in(key, s))``. With a
    ``mesh``, each shard draws ITS OWN params inside a shard_map using
    ``fold_in(key, axis_index(axis))`` — no host materialization, no
    broadcast — and the result is bit-identical to the host loop, because
    the fold-in coordinate is the shard index either way.
    """
    est = registry.get(name)
    if mesh is None:
        chunks = [
            est.init_params(plan, jax.random.fold_in(key, s), dtype)
            for s in range(num_shards)
        ]
        return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *chunks)

    if mesh.shape[axis] != num_shards:
        raise ValueError(
            f"mesh axis {axis!r} has size {mesh.shape[axis]}, "
            f"expected num_shards={num_shards}"
        )

    def local():
        sub = jax.random.fold_in(key, jax.lax.axis_index(axis))
        p = est.init_params(plan, sub, dtype)
        return jax.tree_util.tree_map(lambda a: a[None], p)

    shapes = jax.eval_shape(lambda k: est.init_params(plan, k, dtype), key)
    out_specs = jax.tree_util.tree_map(
        lambda s: P(axis, *(None for _ in s.shape)), shapes
    )
    return shard_map(local, mesh, in_specs=(), out_specs=out_specs)()


# ---------------------------------------------------------------------------
# apply — features partitioned on the "rm_features" axis
# ---------------------------------------------------------------------------
def _reference_apply(est, plan, params, x, *, accum_dtype, use_pallas,
                     interpret, precision=None):
    """Single-device reference: loop shards on host, concat in shard order."""
    s = _num_shards(params)
    scale = jnp.asarray(1.0 / np.sqrt(s), accum_dtype)
    zs = [
        est.apply(plan, _take(params, i), x, accum_dtype=accum_dtype,
                  use_pallas=use_pallas, interpret=interpret,
                  precision=precision) * scale
        for i in range(s)
    ]
    return jnp.concatenate(zs, axis=-1)


def sharded_apply(
    name: str,
    plan: Any,
    params: Any,
    x: jax.Array,
    mesh: Optional[Mesh],
    *,
    axis: str = FEATURE_AXIS,
    accum_dtype=jnp.float32,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
    precision=None,
) -> jax.Array:
    """Featurize ``x [..., d] -> [..., S * output_dim(plan)]`` over the mesh.

    ``x`` is replicated into every shard; shard s computes its sub-map's
    columns and the out-spec concatenates them along the feature axis in
    shard order — the exact layout ``_reference_apply`` produces on one
    device. ``mesh=None`` runs the reference path.
    """
    est = registry.get(name)
    if use_pallas is None:
        use_pallas = not default_interpret()
    if mesh is None:
        return _reference_apply(est, plan, params, x,
                                accum_dtype=accum_dtype,
                                use_pallas=use_pallas, interpret=interpret,
                                precision=precision)
    s = mesh.shape[axis]
    scale = jnp.asarray(1.0 / np.sqrt(s), accum_dtype)

    def local(p, xl):
        z = est.apply(plan, _unstack(p), xl, accum_dtype=accum_dtype,
                      use_pallas=use_pallas, interpret=interpret,
                      precision=precision)
        return z * scale

    in_specs = (
        jax.tree_util.tree_map(
            lambda a: P(axis, *(None for _ in range(a.ndim - 1))), params),
        P(*(None for _ in range(x.ndim))),
    )
    out_specs = P(*(None for _ in range(x.ndim - 1)), axis)
    return shard_map(local, mesh, in_specs, out_specs)(params, x)


# ---------------------------------------------------------------------------
# Gram — partial per-shard Grams, ONE psum over the feature axis
# ---------------------------------------------------------------------------
def sharded_estimate_gram(
    name: str,
    plan: Any,
    params: Any,
    X: jax.Array,
    Y: Optional[jax.Array] = None,
    *,
    mesh: Optional[Mesh] = None,
    axis: str = FEATURE_AXIS,
    row_chunk: int = 4096,
    accum_dtype=jnp.float32,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
    precision=None,
) -> jax.Array:
    """Kernel-matrix estimate ``Z(X) Z(Y)^T`` without gathering features.

    Each shard featurizes the (replicated) rows through its own sub-map —
    row-chunked exactly like the single-device path — and contributes the
    partial Gram ``Z_s(X) Z_s(Y)^T / S``; the single ``psum`` over ``axis``
    is the only cross-device communication. ``mesh=None`` computes the same
    sum serially (the conformance reference).
    """
    est = registry.get(name)
    if use_pallas is None:
        use_pallas = not default_interpret()
    s = _num_shards(params)
    inv_s = 1.0 / s

    def _apply_fn(p_shard):
        return lambda Z: est.apply(
            plan, p_shard, Z, accum_dtype=accum_dtype,
            use_pallas=use_pallas, interpret=interpret,
            precision=precision)

    if mesh is None:
        parts = [
            registry.estimate_gram(_apply_fn(_take(params, i)), X, Y,
                                   row_chunk=row_chunk) * inv_s
            for i in range(s)
        ]
        return sum(parts[1:], parts[0])

    if mesh.shape[axis] != s:
        raise ValueError(
            f"mesh axis {axis!r} has size {mesh.shape[axis]}, but params "
            f"carry {s} shards"
        )

    def local(p, Xl, *rest):
        # the shared registry helper supplies the ONE psum of the partials
        return registry.estimate_gram(
            _apply_fn(_unstack(p)), Xl, rest[0] if rest else None,
            row_chunk=row_chunk, axis_name=axis) * inv_s

    pspecs = jax.tree_util.tree_map(
        lambda a: P(axis, *(None for _ in range(a.ndim - 1))), params)
    rep2 = P(None, None)
    if Y is None:
        fn = shard_map(local, mesh, (pspecs, rep2), rep2)
        return fn(params, X)
    fn = shard_map(local, mesh, (pspecs, rep2, rep2), rep2)
    return fn(params, X, Y)


# ---------------------------------------------------------------------------
# the sharded map object
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ShardedFeatureMap:
    """A feature map whose columns live on the ``"rm_features"`` mesh axis.

    Thin carrier of (estimator name, per-shard plan, stacked params, mesh).
    Duck-types the single-device maps (``apply`` / ``__call__`` /
    ``output_dim`` / ``estimate_gram`` / ``truncation_bias``) so offline
    consumers take any of the three interchangeably; ``sharded=False`` (or
    ``mesh=None``) runs the bit-identical single-device reference.
    """

    estimator: str
    plan: Any
    params: Any                       # stacked [S, ...] leaves
    num_shards: int
    mesh: Optional[Mesh] = None
    axis: str = FEATURE_AXIS

    # -- metadata ------------------------------------------------------------
    @property
    def input_dim(self) -> int:
        return self.plan.input_dim

    @property
    def shard_output_dim(self) -> int:
        return registry.get(self.estimator).output_dim(self.plan)

    @property
    def output_dim(self) -> int:
        return self.num_shards * self.shard_output_dim

    def truncation_bias(self, radius: float) -> float:
        """Per-shard plans share one allocation, so the dropped-degree mass
        of the concatenation equals any single shard's."""
        return registry.get(self.estimator).truncation_bias(self.plan, radius)

    # -- application ---------------------------------------------------------
    def apply(
        self,
        x: jax.Array,
        *,
        sharded: Optional[bool] = None,
        use_pallas: Optional[bool] = None,
        interpret: Optional[bool] = None,
        accum_dtype=jnp.float32,
        precision=None,
    ) -> jax.Array:
        """Featurize ``x [..., d] -> [..., output_dim]`` (all shards'
        columns, concatenated in shard order at ``1/sqrt(S)`` scale).

        ``sharded`` defaults to "mesh present": True runs the one-launch-
        per-shard ``shard_map`` path, False the bit-identical host loop.
        """
        if sharded is None:
            sharded = self.mesh is not None
        return sharded_apply(
            self.estimator, self.plan, self.params, x,
            self.mesh if sharded else None, axis=self.axis,
            accum_dtype=accum_dtype, use_pallas=use_pallas,
            interpret=interpret, precision=precision,
        )

    def __call__(self, x: jax.Array, accum_dtype=jnp.float32) -> jax.Array:
        """Single-device reference path (mirrors RMFeatureMap.__call__)."""
        return self.apply(x, sharded=False, use_pallas=False,
                          accum_dtype=accum_dtype)

    def estimate_gram(
        self,
        X: jax.Array,
        Y: Optional[jax.Array] = None,
        *,
        sharded: Optional[bool] = None,
        row_chunk: int = 4096,
        use_pallas: Optional[bool] = None,
        interpret: Optional[bool] = None,
        precision=None,
    ) -> jax.Array:
        """Kernel-matrix estimate ``Z(X) Z(Y)^T`` without gathering the
        feature columns: per-shard partial Grams, ONE psum (DESIGN.md §10).
        """
        if sharded is None:
            sharded = self.mesh is not None
        return sharded_estimate_gram(
            self.estimator, self.plan, self.params, X, Y,
            mesh=self.mesh if sharded else None, axis=self.axis,
            row_chunk=row_chunk, use_pallas=use_pallas, interpret=interpret,
            precision=precision,
        )


def make_sharded_feature_map(
    kernel,
    input_dim: int,
    num_features: int,
    key: jax.Array,
    *,
    mesh: Optional[Mesh] = None,
    num_shards: Optional[int] = None,
    estimator: str = "rm",
    axis: str = FEATURE_AXIS,
    omega_dtype=jnp.float32,
    device_init: Optional[bool] = None,
    **plan_kwargs,
) -> ShardedFeatureMap:
    """Build a mesh-sharded feature map from any registry estimator.

    The D-feature budget splits into ``num_shards`` (default: the mesh's
    ``axis`` size) sub-maps of D/S features; D must divide evenly so every
    shard traces the same plan. ``device_init=True`` (default when a mesh is
    given) draws each shard's params on its own device via the fold-in rule;
    the resulting stacked tree is already laid out with
    ``distributed.sharding.estimator_param_specs``.
    """
    if num_shards is None:
        if mesh is None:
            raise ValueError("pass mesh= and/or num_shards=")
        num_shards = mesh.shape[axis]
    if num_features % num_shards != 0:
        raise ValueError(
            f"num_features={num_features} must divide evenly over "
            f"{num_shards} feature shards"
        )
    est = registry.get(estimator)
    if not plan_kwargs.get("stratified", True) and "seed" not in plan_kwargs:
        # paper-faithful iid mode draws the degree allocation from the
        # measure — mirror make_feature_map and derive the allocation seed
        # from the key (a fixed seed=0 would freeze the draw across keys,
        # leaving a conditional bias no re-keying or shard-averaging
        # removes). The param key is split off BEFORE the shard fold-ins so
        # host and mesh construction stay bit-identical.
        key, key_deg = jax.random.split(key)
        plan_kwargs["seed"] = int(
            jax.random.randint(key_deg, (), 0, 2**31 - 1))
    plan = est.make_plan(kernel, input_dim, num_features // num_shards,
                         **plan_kwargs)
    if device_init is None:
        device_init = mesh is not None
    params = shard_init_params(
        estimator, plan, key, num_shards, dtype=omega_dtype,
        mesh=mesh if device_init else None, axis=axis,
    )
    if mesh is not None and not device_init:
        specs = estimator_param_specs(params, mesh, axis)
        params = jax.device_put(
            params,
            jax.tree_util.tree_map(
                lambda sp: NamedSharding(mesh, sp), specs,
                is_leaf=lambda sp: isinstance(sp, P)),
        )
    return ShardedFeatureMap(
        estimator=estimator, plan=plan, params=params,
        num_shards=num_shards, mesh=mesh, axis=axis,
    )
