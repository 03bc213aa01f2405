"""The step executor: compiled model functions + the batched decode cache.

Both serving frontends — the legacy bucketed :class:`~repro.serve.engine.
ServingEngine` and the continuous-batching :class:`~repro.serve.scheduler.
Scheduler` — drive the SAME compute object. The executor owns everything
that touches jax:

  * construction-time config validation (causal, estimator registry name,
    precision policy, fusion mode) so a bad config fails here with the
    valid names, not deep inside the first jitted prefill;
  * the prefill bucket ladder (``buckets=``, validated sorted/positive and
    clipped to ``max_len`` so every compiled shape is REACHABLE — a custom
    ``max_len`` below the largest default bucket no longer leaves dead
    entries in the ladder);
  * the batched decode cache (``num_slots`` lanes, spliced per admission)
    and its optional DP-mesh shardings;
  * the jitted prefill/decode calls themselves. Both are MODULE-LEVEL
    jitted functions with the (hashable, frozen) ``ModelConfig`` as a
    static argument, so compilations are shared across executor instances
    — the invariant suite builds hundreds of schedulers per run and pays
    for each (cfg, shape) exactly once per process.

The executor is observability-free: spans/events belong to the frontends,
pure jax belongs here.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.transformer import (
    _split_kind,
    decode_step,
    init_decode_cache,
    prefill,
)

__all__ = ["DEFAULT_BUCKETS", "StepExecutor", "effective_buckets"]

DEFAULT_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048)


def effective_buckets(buckets: Sequence[int], max_len: int) -> Tuple[int, ...]:
    """Clip a bucket ladder to the lengths ``max_len`` can actually serve.

    Ladder entries >= ``max_len`` are unreachable (``submit`` rejects
    prompts of length >= ``max_len``), so the effective ladder is every
    bucket strictly below ``max_len`` plus ``max_len`` itself as the final
    rung — the number of compiled prefill shapes is exactly
    ``len(effective_buckets(...))`` in the worst case.
    """
    ladder = tuple(int(b) for b in buckets)
    if not ladder:
        raise ValueError("buckets must be a non-empty sequence of ints")
    if any(b <= 0 for b in ladder):
        raise ValueError(f"buckets must all be positive, got {ladder}")
    if any(b >= nxt for b, nxt in zip(ladder, ladder[1:])):
        raise ValueError(
            f"buckets must be strictly increasing, got {ladder}")
    return tuple(b for b in ladder if b < max_len) + (int(max_len),)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _decode_compiled(params, cfg: ModelConfig, cache, tokens, positions):
    return decode_step(params, cfg, cache, tokens, positions)


@functools.partial(jax.jit, static_argnames=("cfg", "max_len"))
def _prefill_compiled(params, cfg: ModelConfig, tokens, positions,
                      max_len: int):
    return prefill(params, cfg, {"tokens": tokens, "positions": positions},
                   max_len)


# DP mesh: Pallas kernels cannot be partitioned automatically, so every
# device runs its own lanes of the step under shard_map, with the params
# replicated and the slot axis split over all mesh axes.
def _lane_specs(mesh: Any, tree: Any) -> Any:
    """Specs of the decode cache rules with the slot ("batch") axis over
    every mesh axis and nothing else split; replicated when the slots do
    not divide over the devices."""
    from repro.distributed.sharding import cache_partition_specs

    rules = {"batch": tuple(mesh.axis_names), "heads": None,
             "kv_heads": None, "kv_seq": None, "state": None}
    return cache_partition_specs(tree, mesh, rules=rules)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"))
def _decode_dp_compiled(params, cfg: ModelConfig, mesh, cache, tokens,
                        positions):
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import shard_map

    def _local(p, c, t, pos):
        return decode_step(p, cfg, c, t, pos)

    s = _lane_specs(mesh, {"cache": cache, "tokens": tokens,
                           "positions": positions})
    return shard_map(_local, mesh,
                     in_specs=(P(), s["cache"], s["tokens"], s["positions"]),
                     out_specs=(s["tokens"], s["cache"]))(params, cache,
                                                          tokens, positions)


@functools.partial(jax.jit, static_argnames=("cfg", "max_len", "mesh"))
def _prefill_dp_compiled(params, cfg: ModelConfig, tokens, positions,
                         max_len: int, mesh):
    """The batch-1 prefill, replicated on every device of the mesh. A
    Mosaic kernel in a multi-device jit must sit inside a shard_map even
    when every operand is replicated (DESIGN.md §10)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import shard_map

    def _local(p, t, pos):
        return prefill(p, cfg, {"tokens": t, "positions": pos}, max_len)

    return shard_map(_local, mesh, in_specs=(P(), P(), P()),
                     out_specs=P())(params, tokens, positions)


class StepExecutor:
    """Owns params, the batched decode cache and the compiled step fns.

    Args:
        cfg: frozen model config (validated here).
        params: model params pytree.
        num_slots: decode lanes in the batched cache.
        max_len: per-lane cache length; position ``max_len - 1`` is the
            scratch slot idle lanes park on.
        buckets: prefill bucket ladder (default :data:`DEFAULT_BUCKETS`);
            validated strictly-increasing/positive and clipped to
            ``max_len`` (see :func:`effective_buckets`).
        mesh: optional device mesh for DP decode: params replicated, the
            cache's slot axis split over every mesh axis, and each device
            runs its own lanes under ``shard_map`` (DESIGN.md §10). When
            ``num_slots`` does not divide by the mesh size, every device
            runs every lane.
    """

    def __init__(self, cfg: ModelConfig, params: Any, num_slots: int,
                 max_len: int, *, buckets: Optional[Sequence[int]] = None,
                 mesh: Any = None, feature_generations: int = 1):
        if not cfg.causal:
            raise ValueError("encoder-only models cannot be served "
                             "autoregressively")
        # Fail-early config validation: estimator registry name, precision
        # policy and fusion mode all raise here with the valid options.
        self.estimator: Optional[str] = None
        self.fused_attention = False
        feature_generations = int(feature_generations)
        if feature_generations < 1:
            raise ValueError(
                f"feature_generations must be >= 1, got "
                f"{feature_generations}")
        self.feature_generations = feature_generations
        self.generation_features: Optional[int] = None
        if cfg.attention_mode == "rm":
            from repro.common.dtypes import resolve_precision
            from repro.core import registry
            from repro.models.attention import rm_fuse_enabled

            self.estimator = registry.get(cfg.rm.estimator).name
            resolve_precision(cfg.rm.precision)
            self.fused_attention = rm_fuse_enabled(cfg)
            # Accuracy tiers (docs/adaptive.md): the feature budget splits
            # into fold_in-keyed generations; a tier certifies the prefix
            # of g generations.  The split must be exact so every tier's
            # budget is a whole number of generations.
            if cfg.rm.num_features % feature_generations != 0:
                raise ValueError(
                    f"cfg.rm.num_features={cfg.rm.num_features} must "
                    f"divide evenly into feature_generations="
                    f"{feature_generations} (per-tier budgets are whole "
                    "generations — see docs/adaptive.md)")
            self.generation_features = (cfg.rm.num_features
                                        // feature_generations)
        elif feature_generations != 1:
            raise ValueError(
                f"feature_generations={feature_generations} requires the "
                f"RM attention mode; {cfg.attention_mode!r} has no "
                "feature budget to tier")
        self.cfg = cfg
        self.params = params
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.mesh = mesh
        self.buckets = effective_buckets(
            DEFAULT_BUCKETS if buckets is None else buckets, self.max_len)
        # Prompt-length bucketing applies to attention-family mixers only:
        # they tolerate right-padded prompts at sentinel positions (< 0).
        # SSM mixers carry recurrent state through every position and keep
        # exact lengths (one compile per distinct prompt length).
        mixers = {_split_kind(kind)[0] for kind in cfg.block_pattern}
        self.bucketed = mixers <= {"attn", "mla"}
        self._cache_shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.params = jax.device_put(params, NamedSharding(mesh, P()))
            probe = jax.eval_shape(
                lambda: init_decode_cache(cfg, self.num_slots, self.max_len))
            self._cache_shardings = jax.tree_util.tree_map(
                lambda sp: NamedSharding(mesh, sp), _lane_specs(mesh, probe),
                is_leaf=lambda sp: isinstance(sp, P))
        self.cache = None
        self.reset_cache()

    # -- accuracy tiers -------------------------------------------------------
    def tier_features(self, generations: int) -> int:
        """Feature budget a tier of ``generations`` generations certifies.

        The RM budget splits into ``feature_generations`` equal fold_in-
        keyed blocks (the ``GrowableFeatureMap`` layout); a request at
        tier g is certified against the first ``g * generation_features``
        columns' (eps, delta) bound (docs/adaptive.md).
        """
        if self.generation_features is None:
            raise ValueError(
                "accuracy tiers require the RM attention mode "
                f"(attention_mode={self.cfg.attention_mode!r})")
        g = int(generations)
        if not 1 <= g <= self.feature_generations:
            raise ValueError(
                f"tier generations={generations} out of range [1, "
                f"{self.feature_generations}]")
        return g * self.generation_features

    # -- cache lifecycle ------------------------------------------------------
    @property
    def scratch_position(self) -> int:
        """The cache position idle lanes decode into (output discarded)."""
        return self.max_len - 1

    def reset_cache(self) -> None:
        """(Re)initialize the batched decode cache — fresh lanes, no state.

        The fault-recovery path calls this to respawn after a failed step:
        in-flight decode state is discarded and affected requests replay
        from their prompts (docs/serving.md, recovery contract).
        """
        self.cache = init_decode_cache(self.cfg, self.num_slots, self.max_len)
        if self._cache_shardings is not None:
            self.cache = jax.device_put(self.cache, self._cache_shardings)

    # -- prefill --------------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        """Smallest effective-ladder bucket holding an ``n``-token prompt."""
        if not self.bucketed:
            return int(n)
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(
            f"prompt length {n} exceeds the largest prefill bucket "
            f"({self.buckets[-1]} tokens); shorten the prompt or raise "
            "max_len / extend the bucket ladder")

    def prefill(self, prompt: np.ndarray) -> Tuple[jax.Array, Any, int]:
        """Run one request's prefill; return ``(logits, cache1, bucket)``.

        The prompt is right-padded to its bucket with tokens at sentinel
        position -1, so no real query attends to padding and no decode
        state accumulates it (pinned exactly by
        tests/test_serve_engine.py::test_bucketed_prefill_rm_state_matches_unpadded).
        ``logits`` is the full ``[1, bucket, V]`` array — callers sample
        from the last REAL position ``len(prompt) - 1``.
        """
        t = len(prompt)
        tb = self.bucket_for(t)
        tokens = np.zeros((1, tb), np.int32)
        tokens[0, :t] = np.asarray(prompt, np.int32)
        positions = np.full((1, tb), -1, np.int32)
        positions[0, :t] = np.arange(t, dtype=np.int32)
        if self.mesh is None:
            logits, cache1 = _prefill_compiled(
                self.params, self.cfg, jnp.asarray(tokens),
                jnp.asarray(positions), self.max_len)
        else:
            logits, cache1 = _prefill_dp_compiled(
                self.params, self.cfg, jnp.asarray(tokens),
                jnp.asarray(positions), self.max_len, self.mesh)
        return logits, cache1, tb

    def splice(self, slot: int, cache1: Any) -> None:
        """Write a request's (batch=1) prefill cache into lane ``slot``."""

        def _walk(big, small, path):
            if isinstance(big, dict):
                return {k: _walk(big[k], small[k], path + (k,))
                        for k in big}
            axis = 1 if "groups" in path else 0
            return jax.lax.dynamic_update_index_in_dim(
                big, jnp.take(small, 0, axis=axis).astype(big.dtype), slot,
                axis=axis,
            )

        self.cache = _walk(self.cache, cache1, ())
        if self._cache_shardings is not None:
            # keep the DP layout sticky: the host-level splice loses the
            # slot-axis sharding of the updated leaves
            self.cache = jax.device_put(self.cache, self._cache_shardings)

    # -- decode ---------------------------------------------------------------
    def decode(self, tokens: jax.Array, positions: jax.Array) -> jax.Array:
        """One batched decode step over ALL lanes; updates the cache.

        ``tokens`` is ``[num_slots, 1]`` int32, ``positions``
        ``[num_slots]`` int32 (idle lanes at :attr:`scratch_position`).
        Returns logits ``[num_slots, 1, V]``.
        """
        if self.mesh is None:
            logits, self.cache = _decode_compiled(
                self.params, self.cfg, self.cache, tokens, positions)
        else:
            logits, self.cache = _decode_dp_compiled(
                self.params, self.cfg, self.mesh, self.cache, tokens,
                positions)
        return logits
