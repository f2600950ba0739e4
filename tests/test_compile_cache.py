"""The persistent compile cache goes to one fixed place (launch/compile_cache)."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def updates(monkeypatch):
    """Record jax.config updates instead of applying them, so no test here
    turns the cache on for the rest of the worker."""
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.__setitem__(name, value))
    return seen


def test_env_dir_is_honoured(monkeypatch, tmp_path, updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates["jax_compilation_cache_dir"] == str(tmp_path)


def test_default_dir_is_fixed_in_checkout(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = Path(__file__).resolve().parents[1]
    path = compile_cache.enable_compile_cache()
    assert path == str(root / ".jax_cache")
    assert updates["jax_compilation_cache_dir"] == path
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
