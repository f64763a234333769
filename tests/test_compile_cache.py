"""The persistent compilation cache helper (launch/compile_cache.py): the
environment variable wins untouched; otherwise a fixed in-checkout
directory."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_is_used_and_nothing_is_set(monkeypatch, tmp_path,
                                            restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_uses_fixed_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    root = Path(__file__).resolve().parents[1]
    want = str(root / ".jax_cache")
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same path on every call: the path is part of the cache key
    assert compile_cache.enable() == want
