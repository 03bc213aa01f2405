"""Progressive feature doubling — grow D online without redrawing.

The adaptive-accuracy subsystem (ROADMAP open item 3, docs/adaptive.md)
needs the feature budget to be a DIAL, not a constructor constant: when the
drift monitor reports an (eps, delta) violation, the serving/training loop
must buy more accuracy without invalidating the features it already
computed.  The construction is the ``fold_in``-keyed shard draw that
``distributed/estimator.py`` already pins for mesh shards, reused over a
*generation* index instead of a device coordinate:

    * one per-generation plan of ``base_features`` columns (the same
      hashable plan for every generation, so growth never retraces);
    * generation g's params are ``init_params(plan, fold_in(key, g))`` —
      they depend only on (key, g), never on when g was materialized, so
      growing from G to 2G generations APPENDS draws and leaves
      generations [0, G) bit-identical;
    * ``Z(x) = concat_g Z_g(x) / sqrt(G)`` — each generation is an unbiased
      estimator of the kernel, so the concatenation at ``1/sqrt(G)`` is the
      unbiased G-fold average.  The *raw* (unscaled) feature prefix is
      bit-identical across growth; the scaled output differs from the old
      one only by the single global ``sqrt(G_old / G_new)`` factor.

Because the fold-in coordinate doubles as the shard index, a
``GrowableFeatureMap`` at G generations computes the same raw feature
layout as ``ShardedFeatureMap`` with S = G shards of the same plan and key
— growth and sharding are one contract.

``eps_at`` tightens monotonically in the generation count (Theorem 12's
certified error at the current total budget), which is what lets
``obs.DriftMonitor.recommend()`` → ``grow()`` form a control loop: every
doubling multiplies the certified eps by ``~1/sqrt(2)``.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import registry
from repro.core.bounds import HoeffdingConstants, constants_for
from repro.core.maclaurin import DotProductKernel

__all__ = ["GrowableFeatureMap", "make_growable_feature_map"]


def _stack_params(est, plan, key_data: np.ndarray, start: int, stop: int,
                  dtype) -> Any:
    """Stacked params for generations [start, stop): leaf g is drawn with
    ``fold_in(key, g)`` — the exact rule ``shard_init_params`` pins for
    mesh shards, so a generation's draw depends only on (key, g)."""
    key = jnp.asarray(key_data, jnp.uint32)
    chunks = [
        est.init_params(plan, jax.random.fold_in(key, g), dtype)
        for g in range(start, stop)
    ]
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *chunks)


def _concat_stacked(old: Any, new: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b], axis=0), old, new)


@dataclasses.dataclass
class GrowableFeatureMap:
    """A feature map whose budget doubles in place, prefix-preserving.

    Thin carrier of (estimator name, one per-generation plan, stacked
    ``[G, ...]`` params, the base PRNG key all generations fold from, and
    the bound context).  Duck-types the other map objects (``apply`` /
    ``__call__`` / ``output_dim`` / ``estimate_gram`` /
    ``truncation_bias``) so offline consumers take it interchangeably.
    """

    estimator: str
    plan: Any
    params: Any                        # stacked [n_generations, ...] leaves
    n_generations: int
    key_data: np.ndarray               # uint32 key the generations fold from
    kernel: Optional[DotProductKernel] = None
    radius: float = 1.0
    measure: str = "geometric"
    p: float = 2.0
    omega_dtype: Any = jnp.float32

    # -- metadata ------------------------------------------------------------
    @property
    def input_dim(self) -> int:
        return self.plan.input_dim

    @property
    def generation_output_dim(self) -> int:
        return registry.get(self.estimator).output_dim(self.plan)

    @property
    def output_dim(self) -> int:
        return self.n_generations * self.generation_output_dim

    def truncation_bias(self, radius: float) -> float:
        """Generations share one plan, so the dropped-degree mass of the
        concatenation equals any single generation's."""
        return registry.get(self.estimator).truncation_bias(
            self.plan, radius)

    # -- bound side ----------------------------------------------------------
    def constants(self) -> HoeffdingConstants:
        if self.kernel is None:
            raise ValueError(
                "this GrowableFeatureMap carries no kernel (e.g. it was "
                "rebuilt via from_json without one); pass kernel= to "
                "from_json to restore eps_at/required_generations")
        return constants_for(self.kernel, self.radius, self.input_dim,
                             self.p)

    def eps_at(self, delta: float,
               num_features: Optional[int] = None) -> float:
        """Theorem 12's certified uniform error at ``num_features``
        (default: the CURRENT total budget).  Monotone non-increasing in
        the generation count — the conformance suite pins this."""
        d = self.output_dim if num_features is None else num_features
        return self.constants().eps_at(d, delta, self.measure)

    def required_generations(self, eps: float, delta: float) -> int:
        """Smallest generation count whose total budget certifies eps."""
        d_req = self.constants().required_d(eps, delta, self.measure)
        per_gen = self.generation_output_dim
        return max(-(-d_req // per_gen), 1)

    # -- growth --------------------------------------------------------------
    def grow(self, factor: int = 2) -> "GrowableFeatureMap":
        """Multiply the generation count by ``factor`` WITHOUT redrawing.

        Returns a new map whose generations ``[0, n_generations)`` carry
        the exact same params (the stacked prefix is untouched); only
        generations ``[n_generations, factor * n_generations)`` are new
        draws, keyed by their generation index alone.
        """
        if factor < 2:
            raise ValueError(f"growth factor must be >= 2, got {factor}")
        return self.grow_to_generations(self.n_generations * factor)

    def grow_to_generations(self, n_generations: int) -> "GrowableFeatureMap":
        if n_generations < self.n_generations:
            raise ValueError(
                f"cannot shrink: have {self.n_generations} generations, "
                f"asked for {n_generations}")
        if n_generations == self.n_generations:
            return self
        est = registry.get(self.estimator)
        new = _stack_params(est, self.plan, self.key_data,
                            self.n_generations, n_generations,
                            self.omega_dtype)
        return dataclasses.replace(
            self,
            params=_concat_stacked(self.params, new),
            n_generations=n_generations,
        )

    def grow_to(self, num_features: int) -> "GrowableFeatureMap":
        """Grow until ``output_dim >= num_features`` (whole generations)."""
        per_gen = self.generation_output_dim
        return self.grow_to_generations(
            max(-(-num_features // per_gen), self.n_generations))

    # -- application ---------------------------------------------------------
    def apply(
        self,
        x: jax.Array,
        *,
        rescale: bool = True,
        accum_dtype=jnp.float32,
        use_pallas: Optional[bool] = None,
        interpret: Optional[bool] = None,
        precision=None,
    ) -> jax.Array:
        """Featurize ``x [..., d] -> [..., output_dim]``.

        Generation g's columns occupy the contiguous block
        ``[g * generation_output_dim, (g+1) * generation_output_dim)``.
        ``rescale=False`` returns the RAW concatenation (no ``1/sqrt(G)``)
        — the quantity that is bit-identical across ``grow()``; the scaled
        output is exactly ``raw * (1/sqrt(G))``, one global multiply.
        """
        est = registry.get(self.estimator)
        if use_pallas is None:
            from repro.kernels.common import default_interpret

            use_pallas = not default_interpret()
        zs = [
            est.apply(self.plan,
                      jax.tree_util.tree_map(lambda a: a[g], self.params),
                      x, accum_dtype=accum_dtype, use_pallas=use_pallas,
                      interpret=interpret, precision=precision)
            for g in range(self.n_generations)
        ]
        raw = jnp.concatenate(zs, axis=-1)
        if not rescale:
            return raw
        return raw * jnp.asarray(1.0 / np.sqrt(self.n_generations),
                                 accum_dtype)

    def __call__(self, x: jax.Array, accum_dtype=jnp.float32) -> jax.Array:
        return self.apply(x, use_pallas=False, accum_dtype=accum_dtype)

    def estimate_gram(
        self,
        X: jax.Array,
        Y: Optional[jax.Array] = None,
        *,
        row_chunk: int = 4096,
        use_pallas: Optional[bool] = None,
        interpret: Optional[bool] = None,
        precision=None,
    ) -> jax.Array:
        """Kernel-matrix estimate without materializing the concatenation:
        per-generation partial Grams summed at ``1/G`` (the serial twin of
        the sharded psum reduction)."""
        est = registry.get(self.estimator)
        if use_pallas is None:
            from repro.kernels.common import default_interpret

            use_pallas = not default_interpret()
        inv_g = 1.0 / self.n_generations

        def _apply_fn(g):
            p = jax.tree_util.tree_map(lambda a: a[g], self.params)
            return lambda Z: est.apply(
                self.plan, p, Z, use_pallas=use_pallas,
                interpret=interpret, precision=precision)

        parts = [
            registry.estimate_gram(_apply_fn(g), X, Y,
                                   row_chunk=row_chunk) * inv_g
            for g in range(self.n_generations)
        ]
        return sum(parts[1:], parts[0])

    # -- serialization -------------------------------------------------------
    def to_json(self) -> str:
        """Growth state as JSON: the per-generation plan (via the shared
        plan serialization), the base key, and the generation count — the
        params are NOT stored; they are a pure function of (plan, key, G)
        and are redrawn bit-identically by ``from_json``."""
        ptype = type(self.plan)
        return json.dumps({
            "estimator": self.estimator,
            "plan_type": [ptype.__module__, ptype.__qualname__],
            "plan": json.loads(self.plan.to_json()),
            "n_generations": self.n_generations,
            "key_data": np.asarray(self.key_data).tolist(),
            "radius": self.radius,
            "measure": self.measure,
            "p": self.p,
        })

    @classmethod
    def from_json(cls, s: str,
                  kernel: Optional[DotProductKernel] = None,
                  omega_dtype=jnp.float32) -> "GrowableFeatureMap":
        d = json.loads(s)
        mod, qual = d["plan_type"]
        plan_cls = getattr(importlib.import_module(mod), qual)
        plan = plan_cls.from_json(json.dumps(d["plan"]))
        key_data = np.asarray(d["key_data"], np.uint32)
        est = registry.get(d["estimator"])
        params = _stack_params(est, plan, key_data, 0, d["n_generations"],
                               omega_dtype)
        return cls(
            estimator=d["estimator"], plan=plan, params=params,
            n_generations=d["n_generations"], key_data=key_data,
            kernel=kernel, radius=d["radius"], measure=d["measure"],
            p=d["p"], omega_dtype=omega_dtype,
        )


def make_growable_feature_map(
    kernel: DotProductKernel,
    input_dim: int,
    key: jax.Array,
    *,
    base_features: int = 64,
    n_generations: int = 1,
    eps: Optional[float] = None,
    delta: Optional[float] = None,
    estimator: str = "rm",
    p: float = 2.0,
    measure: str = "geometric",
    h01: bool = False,
    n_max: int = 24,
    radius: float = 1.0,
    omega_dtype=jnp.float32,
    stratified: bool = True,
    precision=None,
) -> GrowableFeatureMap:
    """Build a growable map from any registry estimator.

    Either start from an explicit ``n_generations`` of ``base_features``
    each, or pass accuracy targets ``eps``/``delta`` and get the smallest
    generation count whose total budget Theorem 12 certifies at
    (eps, delta) — the same inversion ``select_budget`` uses.
    """
    if omega_dtype is None or precision is not None:
        if precision is not None:
            from repro.common.dtypes import resolve_precision

            omega_dtype = resolve_precision(precision).compute_dtype
        elif omega_dtype is None:
            omega_dtype = jnp.float32
    est = registry.get(estimator)
    plan = est.make_plan(
        kernel, input_dim, base_features,
        p=p, measure=measure, h01=h01, n_max=n_max, radius=radius,
        stratified=stratified,
    )
    key_data = np.asarray(key, np.uint32)
    fm = GrowableFeatureMap(
        estimator=estimator, plan=plan,
        params=_stack_params(est, plan, key_data, 0, 1, omega_dtype),
        n_generations=1, key_data=key_data, kernel=kernel, radius=radius,
        measure=measure, p=p, omega_dtype=omega_dtype,
    )
    if eps is not None or delta is not None:
        if eps is None or delta is None:
            raise ValueError("pass BOTH eps and delta (or neither)")
        n_generations = fm.required_generations(eps, delta)
    return fm.grow_to_generations(max(n_generations, 1))
