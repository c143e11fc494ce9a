"""Where the command-line entry points put JAX's persistent compile cache."""
from __future__ import annotations

import jax
import pytest

from repro.utils import compile_cache


@pytest.fixture
def cache_dir_config():
    """Restore the process-wide cache-directory setting after the test."""
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_env_dir_is_left_to_jax(monkeypatch, cache_dir_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_unset_env_uses_fixed_checkout_dir(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    # one fixed path, named by the checkout alone
    assert compile_cache.CHECKOUT_CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.CHECKOUT_CACHE_DIR.parent / "pyproject.toml").exists()
    assert compile_cache.enable_compile_cache() == got


def test_checkout_cache_dir_is_git_ignored():
    ignore = (compile_cache.CHECKOUT_CACHE_DIR.parent / ".gitignore").read_text()
    assert ".jax_cache/" in ignore.split()
