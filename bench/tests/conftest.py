"""Fixtures of the benchmark's CPU tests."""
import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache


@pytest.fixture
def jax_config_restored():
    """A run of the harness turns JAX's persistent cache on for the repo's
    cache directory; put the process's settings back afterwards, so the
    tests that share this worker see them as they were."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    if env is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = env
    compilation_cache.reset_cache()
