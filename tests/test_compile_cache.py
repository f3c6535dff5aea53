"""Where the entry points put JAX's persistent compilation cache."""
import jax
import pytest

from repro.utils import compile_cache


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_unset_env_uses_fixed_dir_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CACHE_DIR)
    assert compile_cache.CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.CACHE_DIR.parent / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path
    # a second call (a second run in the same checkout) lands on the same dir
    assert compile_cache.enable_compile_cache() == path


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_dir, tmp_path):
    """JAX reads the variable itself at import; the helper sets nothing."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "as-read"))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "as-read")
