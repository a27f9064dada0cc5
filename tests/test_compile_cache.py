"""The entry points' compile-cache rule (repro.launch.compile_cache)."""
import jax
import pytest

from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache

_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def restore_config():
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("env_dir", [None, "/some/cache/dir"])
def test_cache_dir_is_the_env_or_the_fixed_checkout_path(
        monkeypatch, restore_config, env_dir):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    path = enable_compile_cache()
    if env_dir is None:
        assert path == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert DEFAULT_DIR.name == ".jax_cache"
        assert (DEFAULT_DIR.parent / "chip_smoke.py").exists()
    else:
        # JAX reads the variable itself; the helper names no other directory
        assert path == env_dir
        assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
