"""Where the persistent compilation cache goes: a directory named by
``JAX_COMPILATION_CACHE_DIR`` is left to JAX, otherwise a fixed directory
at the root of the checkout that git ignores."""

from __future__ import annotations

import pathlib

import jax
import pytest

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_uses_fixed_dir_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = enable_compile_cache()
    assert got == str(ROOT / ".jax_cache") == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert enable_compile_cache() == got        # the same path every run
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
