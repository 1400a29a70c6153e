"""The entry points' persistent-cache switch (ggrs_tpu/utils/compile_cache.py):
an env-provided directory is left to JAX untouched; otherwise the cache
goes to one fixed path inside the checkout."""

import jax
import pytest

from ggrs_tpu.utils.compile_cache import REPO_CACHE_DIR, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins_and_nothing_is_set(monkeypatch, restore_cache_dir,
                                         tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_repo_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    assert REPO_CACHE_DIR.parent.joinpath("chip_smoke.py").exists()
    assert enable_compile_cache() == str(REPO_CACHE_DIR)  # stable
