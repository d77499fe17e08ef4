"""Where the persistent compilation cache goes (launch/compile_cache.py)."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_config():
    """Restore JAX's cache directory after the test sets it."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_directory_wins(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; nothing here sets another directory
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_fixed_inside_the_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = Path(__file__).resolve().parents[1]
    want = str(repo / ".jax_cache")
    assert compile_cache.setup_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same path on every call: no temporary name, pid or time in it
    assert compile_cache.setup_compile_cache() == want
