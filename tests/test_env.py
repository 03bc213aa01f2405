"""Process-level placement helpers in ``repro.common.env``."""
from pathlib import Path

import jax
import pytest

from repro.common import env

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture()
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = env.use_compile_cache()
    assert got == str(CHECKOUT / ".xla-cache")
    assert jax.config.jax_compilation_cache_dir == got


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path, cache_dir_config):
    """With the variable set, JAX reads it and the helper sets no other
    directory."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert env.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_block_cache_default_is_inside_checkout(monkeypatch):
    from repro.kernels import common

    monkeypatch.delenv("REPRO_BLOCK_CACHE", raising=False)
    assert common.block_cache_path() == CHECKOUT / "feature_blocks.json"
