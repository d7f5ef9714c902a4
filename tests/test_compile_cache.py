"""Placement of JAX's persistent compilation cache (launch/compile_cache)."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_config():
    """Restore the process-wide cache directory after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_config_is_left_alone(monkeypatch, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    assert compile_cache.enable_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_uses_fixed_dir_in_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = Path(__file__).resolve().parents[1]
    path = compile_cache.enable_compile_cache()
    assert path == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # the same path on every call: nothing per-process in it
    assert compile_cache.enable_compile_cache() == path
