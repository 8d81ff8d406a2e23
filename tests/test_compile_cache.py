"""The persistent compilation cache is placed from outside the program:
``JAX_COMPILATION_CACHE_DIR`` when set, else one fixed, gitignored
directory inside the checkout."""

import pathlib

import jax
import pytest

from repro.launch.compile_cache import (CACHE_ENV, DEFAULT_CACHE_DIR,
                                        configure_compile_cache)

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_is_left_to_jax(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_default_is_fixed_gitignored_dir_in_checkout(monkeypatch,
                                                     restore_cache_dir):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    assert configure_compile_cache() == str(DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
    assert DEFAULT_CACHE_DIR == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
