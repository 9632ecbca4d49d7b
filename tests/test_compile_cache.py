"""``launch.compile_cache.enable_compile_cache``: JAX's own reading of
``JAX_COMPILATION_CACHE_DIR`` wins; otherwise one fixed directory inside
the checkout."""
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    """Restore the process-wide cache directory after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_set_changes_nothing(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_env_var_unset_uses_fixed_dir_in_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = enable_compile_cache(), enable_compile_cache()
    assert first == second == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
