"""Where the entry points put JAX's persistent compile cache."""
import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_goes_to_the_checkout_by_default(monkeypatch,
                                               cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    compile_cache.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        root, ".jax_cache")


def test_environment_variable_wins(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    jax.config.update("jax_compilation_cache_dir", None)
    compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir is None
