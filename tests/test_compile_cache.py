"""The persistent compilation cache is placed from outside when it can be."""
from __future__ import annotations

from pathlib import Path

import jax
import pytest

from repro.compile_cache import use_compile_cache

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env", [None, "/x"], ids=["checkout", "env"])
def test_cache_dir(env, monkeypatch, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert use_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        assert use_compile_cache() == env
        # JAX reads the variable itself; nothing here is changed
        assert jax.config.jax_compilation_cache_dir == before
