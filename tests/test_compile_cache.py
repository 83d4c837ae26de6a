"""The persistent compilation cache goes where JAX_COMPILATION_CACHE_DIR says,
and otherwise to one fixed directory inside the checkout."""

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_honoured(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == str(compile_cache.REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == first
    repo = compile_cache.REPO_CACHE_DIR.parent
    assert (repo / "chip_smoke.py").exists()
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
