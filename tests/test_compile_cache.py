"""The persistent compilation cache goes where JAX_COMPILATION_CACHE_DIR
says; without it, to one fixed git-ignored directory in the checkout."""
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_names_the_cache(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax-cache")
    was = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == was   # JAX reads the env


def test_default_cache_is_in_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert use_compile_cache() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
