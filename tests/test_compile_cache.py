"""Where ``repro.launch.compile_cache`` puts JAX's persistent cache."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_config():
    """Restore the process-wide cache setting: tests never keep it on."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_environment_variable_wins(monkeypatch, cache_dir_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_path_in_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    checkout = compile_cache.DEFAULT_CACHE_DIR.parent
    assert (checkout / "src" / "repro" / "launch").is_dir()
    assert ".jax_cache/" in (checkout / ".gitignore").read_text().split()
