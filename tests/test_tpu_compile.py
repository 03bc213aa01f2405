"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Every kernel on the served path is lowered and compiled for a DESCRIBED
``v5e:2x2`` topology — no chip attached — at the real widths of the served
``qwen3-1.7b`` RM attention: head_dim 128, F=256 features, n_max 8,
prefill buckets up to 2048, decode with 4 slots x 16 heads. The TPU
compiler refuses here what the Pallas interpreter cannot see (block shapes
off the (8, 128) tiling, VMEM overuse), and ``tpu_custom_call`` in the
compiled text proves the kernel compiled rather than ran interpreted.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ExponentialDotProductKernel, registry

D, F, N_MAX = 128, 256, 8          # head_dim, num_features, n_max
HEADS, SLOTS, BUCKET = 16, 4, 2048
KERN = ExponentialDotProductKernel(1.0)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    with _no_compile_cache():
        return jax.jit(fn).lower(*args).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _plan(name):
    return registry.get(name).make_plan(KERN, D, F, measure="proportional",
                                        n_max=N_MAX, seed=0)


def _param_specs(sharding, name, plan):
    shapes = jax.eval_shape(
        lambda: registry.get(name).init_params(plan, jax.random.PRNGKey(0)))
    return jax.tree_util.tree_map(
        lambda a: _spec(sharding, a.shape, a.dtype), shapes)


def _rm_packed():
    """Packed [max_degree, F, d] layout and host column metadata."""
    plan = _plan("rm")
    params = registry.get("rm").init_params(plan, jax.random.PRNGKey(0))
    w, col_deg, col_scale = registry.get("rm").pack_fused(plan, params)
    return w.shape, np.asarray(col_deg), np.asarray(col_scale)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_rm_feature_fused_compiles(one_chip, dtype):
    from repro.kernels.rm_feature.ops import rm_feature_fused

    w_shape, _, _ = _rm_packed()
    text = _compiled_text(
        lambda x, w, deg, sc: rm_feature_fused(
            x, w, deg, sc, use_pallas=True, interpret=False),
        _spec(one_chip, (4096, D), dtype),
        _spec(one_chip, w_shape, dtype),
        _spec(one_chip, (w_shape[1],), jnp.int32),
        _spec(one_chip, (w_shape[1],), jnp.float32))
    assert "tpu_custom_call" in text


def test_rm_attention_fused_prefill_compiles(one_chip):
    from repro.kernels.rm_attention.ops import rm_attention_fused_prefill

    w_shape, col_deg, col_scale = _rm_packed()
    qk = _spec(one_chip, (1, HEADS, BUCKET, D), jnp.float32)
    text = _compiled_text(
        lambda q, k, v, w, kvalid: rm_attention_fused_prefill(
            q, k, v, w, col_deg, col_scale, kvalid=kvalid,
            use_pallas=True, interpret=False),
        qk, qk,
        _spec(one_chip, (1, HEADS, BUCKET, D), jnp.bfloat16),
        _spec(one_chip, w_shape, jnp.float32),
        _spec(one_chip, (1, BUCKET), jnp.float32))
    assert "tpu_custom_call" in text


def test_rm_attention_fused_decode_step_compiles(one_chip):
    from repro.kernels.rm_attention.ops import rm_attention_fused_decode_step

    w_shape, col_deg, col_scale = _rm_packed()
    f = w_shape[1]
    row = _spec(one_chip, (SLOTS, HEADS, D), jnp.float32)
    text = _compiled_text(
        lambda q, k, v, s, n, w: rm_attention_fused_decode_step(
            q, k, v, s, n, w, col_deg, col_scale,
            use_pallas=True, interpret=False),
        row, row,
        _spec(one_chip, (SLOTS, HEADS, D), jnp.bfloat16),
        _spec(one_chip, (SLOTS, HEADS, f, D), jnp.float32),
        _spec(one_chip, (SLOTS, HEADS, f), jnp.float32),
        _spec(one_chip, w_shape, jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name", ["tensor_sketch", "ctr", "structured"])
def test_estimator_apply_compiles(one_chip, name):
    est = registry.get(name)
    plan = _plan(name)
    text = _compiled_text(
        lambda p, x: est.apply(plan, p, x, use_pallas=True,
                               interpret=False),
        _param_specs(one_chip, name, plan),
        _spec(one_chip, (4096, D), jnp.float32))
    assert "tpu_custom_call" in text
