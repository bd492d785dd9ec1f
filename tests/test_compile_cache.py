"""``launch.compile_cache``: where the entry points keep JAX's persistent
compilation cache.  Each test restores the process's cache configuration."""

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.compile_cache import ENV_VAR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_honored(monkeypatch, tmp_path, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other path
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(ENV_VAR, raising=False)
    path = enable_compile_cache()
    assert Path(path) == REPO / ".jax_cache" == compile_cache.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path          # the same on every call


def test_cache_dir_is_gitignored():
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
