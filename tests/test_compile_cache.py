"""Where the entry points put JAX's persistent compilation cache."""
import pathlib

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_config():
    """Restore the process-wide cache directory after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, tmp_path,
                                              cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no path of its own
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_fixed_path_in_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    root = pathlib.Path(__file__).resolve().parents[1]
    assert path == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable() == path      # same path every call
