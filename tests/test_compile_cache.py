"""enable_compile_cache: $JAX_COMPILATION_CACHE_DIR when set, else the
fixed ``.jax_cache`` at the root of the checkout."""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.utils.compile_cache import DEFAULT_DIR, enable_compile_cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    prev = {k: getattr(jax.config, k) for k in keys}
    compilation_cache.reset_cache()
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_cache_goes_where_the_env_says(monkeypatch, tmp_path,
                                       restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    assert any(tmp_path.iterdir())


def test_cache_defaults_to_the_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    assert DEFAULT_DIR.name == ".jax_cache"
    assert (DEFAULT_DIR.parent / "chip_smoke.py").is_file()
    assert ".jax_cache/" in (DEFAULT_DIR.parent / ".gitignore").read_text()
