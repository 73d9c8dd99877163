"""The compile-cache rule: ``JAX_COMPILATION_CACHE_DIR`` wins when set;
otherwise one fixed directory inside the checkout."""

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_wins_and_nothing_is_set(monkeypatch, restore_cache_dir,
                                         tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_dir_in_the_checkout(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    root = Path(__file__).resolve().parents[1]
    first = compile_cache.enable_compile_cache()
    assert first == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.enable_compile_cache() == first
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
