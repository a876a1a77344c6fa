"""The launchers' persistent compile cache location (launch/compile_cache)."""
import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == "/cache/from/env"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_dir_in_the_repo(monkeypatch,
                                            restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.enable() == want      # same path on every call
