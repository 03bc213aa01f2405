"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run forces 512 host devices via XLA_FLAGS before any jax import, while
tests/benches must keep seeing 1 device.

Every mesh in the repo is built here. Bare ``jax.make_mesh`` makes Explicit
axes, which ``with_sharding_constraint`` and the name-rule table in
``repro.distributed.sharding`` do not expect, so every axis is Auto.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Auto-axis mesh of ``shape`` over the local devices."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_host_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist locally (CPU tests: 1..8 devices)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))


def make_feature_mesh(num_shards: Optional[int] = None):
    """1-axis mesh whose axis IS the logical feature axis ``"rm_features"``.

    The sharded estimator path (``repro.distributed.estimator``) partitions
    random-feature columns over this axis: each device owns one shard's
    params and feature columns, and Gram estimation reduces with a single
    ``psum``. Defaults to all local devices (8 under
    ``--xla_force_host_platform_device_count=8``).
    """
    from repro.distributed.sharding import FEATURE_AXIS

    n = len(jax.devices()) if num_shards is None else num_shards
    return make_mesh((n,), (FEATURE_AXIS,))
