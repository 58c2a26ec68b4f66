"""The persistent compilation cache shared by every entry point."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_dir_is_used_and_nothing_is_set(
    monkeypatch, tmp_path, restore_cache_dir
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(
    monkeypatch, restore_cache_dir
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert (compile_cache.CHECKOUT / "pyproject.toml").is_file()
    assert path == str(compile_cache.CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path


def test_outside_a_checkout_the_env_var_is_required(
    monkeypatch, tmp_path, restore_cache_dir
):
    """An installed package must not derive a shared path from its
    environment's directories."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "CHECKOUT", tmp_path)
    before = jax.config.jax_compilation_cache_dir
    with pytest.raises(RuntimeError, match="JAX_COMPILATION_CACHE_DIR"):
        compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before
