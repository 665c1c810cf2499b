"""Where the on-disk caches go: one fixed directory inside the checkout,
or wherever ``JAX_COMPILATION_CACHE_DIR`` / ``REPRO_PVQ_TUNE_CACHE`` say."""

from __future__ import annotations

import jax
import pytest

from repro.kernels import autotune
from repro.runtime import caches


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_into_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = caches.enable_compile_cache()
    assert got == str(caches.COMPILE_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert (caches.REPO_ROOT / "src" / "repro" / "runtime" / "caches.py").is_file()


def test_compile_cache_env_wins_and_nothing_is_set(
    monkeypatch, tmp_path, restore_cache_dir
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert caches.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_tune_cache_defaults_next_to_the_compile_cache(monkeypatch, tmp_path):
    monkeypatch.delenv("REPRO_PVQ_TUNE_CACHE", raising=False)
    assert autotune.cache_path() == caches.TUNE_CACHE_FILE
    assert caches.TUNE_CACHE_FILE.parent == caches.COMPILE_CACHE_DIR.parent
    monkeypatch.setenv("REPRO_PVQ_TUNE_CACHE", str(tmp_path / "t.json"))
    assert autotune.cache_path() == tmp_path / "t.json"


def test_gitignore_lists_both_caches():
    lines = (caches.REPO_ROOT / ".gitignore").read_text().split()
    assert f"{caches.COMPILE_CACHE_DIR.name}/" in lines
    assert caches.TUNE_CACHE_FILE.name in lines
