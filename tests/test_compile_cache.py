"""The persistent compilation cache sits at one fixed path: the
environment's ``JAX_COMPILATION_CACHE_DIR`` when set, else a directory
of the checkout."""

from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.compile_cache import CHECKOUT_CACHE, enable_compile_cache

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def cache_dir_restored():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def test_checkout_cache_is_one_fixed_path(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert first == CHECKOUT_CACHE == REPO / ".jax_cache"
    assert enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == str(first)


def test_environment_cache_is_left_to_jax(monkeypatch, tmp_path,
                                         cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before
