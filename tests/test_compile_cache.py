"""Where the entry points' persistent compilation cache lands."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture()
def restore_cache_config():
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_enable_compilation_cache)
    yield
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_enable_compilation_cache", was[1])


def test_env_dir_is_used_and_nothing_else_is_set(monkeypatch, tmp_path,
                                                 restore_cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX itself reads the variable; the helper names no directory
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_at_repo_root(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.REPO_CACHE_DIR)
    assert compile_cache.REPO_CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.REPO_CACHE_DIR.parent / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_enable_compilation_cache
