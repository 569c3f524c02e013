"""The entry points' persistent compile cache: ``JAX_COMPILATION_CACHE_DIR``
when it is set, otherwise one fixed, git-ignored path in the checkout."""

import pathlib

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", prev[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev[1])
    cc.reset_cache()


def test_env_dir_is_used_and_nothing_else_is_set(monkeypatch, tmp_path,
                                                 restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_uses_one_fixed_ignored_checkout_path(monkeypatch,
                                                        restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.enable_compile_cache() == got
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
