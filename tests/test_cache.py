"""The persistent compilation cache is placed from outside the library."""

import jax
import pytest

from repro import cache


@pytest.fixture
def jax_cache_config():
    """Restore the two JAX options ``configure_compile_cache`` sets."""
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])


def test_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch,
                                                         jax_cache_config):
    monkeypatch.delenv(cache.ENV, raising=False)
    path = cache.configure_compile_cache()
    assert path == str(cache.DEFAULT_DIR)
    assert (cache.DEFAULT_DIR.parent / "pyproject.toml").exists()
    assert jax.config.jax_compilation_cache_dir == path
    # a second call (another entry point in the same process) agrees
    assert cache.configure_compile_cache() == path


def test_cache_dir_from_the_environment_is_left_to_jax(monkeypatch, tmp_path,
                                                        jax_cache_config):
    monkeypatch.setenv(cache.ENV, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert cache.configure_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; nothing here overrides it
    assert jax.config.jax_compilation_cache_dir is None
